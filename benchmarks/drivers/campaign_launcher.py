"""Start the system's query campaign (``cli.process_query``) as users
do, with a profiler that the benchmark can switch on and off from
outside, and a report of the devices.

    python campaign_launcher.py <trace-dir|-> <device-file> \\
        <cli.process_query arguments...>

Before the campaign the device JAX finds is written to
``<device-file>`` (``platform``, ``kind``, ``count``); after it, the
same with ``peak_bytes``, each local device's ``peak_bytes_in_use``.
SIGUSR1 starts ``jax.profiler`` into ``<trace-dir>`` and SIGUSR2 stops
it (``gateway_launcher.profiler_thread``); a profile still recording
when the campaign ends is stopped before the process exits. With ``-``
the signals are ignored. Everything else is the campaign's own
``main``.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading

from drivers.gateway_launcher import profiler_thread


def write_json(path: str, obj: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def main() -> int:
    trace_dir, device_path, argv = sys.argv[1], sys.argv[2], sys.argv[3:]
    profiler = None
    if trace_dir != "-":
        os.makedirs(trace_dir, exist_ok=True)
        start, stop = threading.Event(), threading.Event()
        signal.signal(signal.SIGUSR1, lambda *_: start.set())
        signal.signal(signal.SIGUSR2, lambda *_: stop.set())
        profiler = threading.Thread(target=profiler_thread,
                                    args=(trace_dir, start, stop),
                                    daemon=True, name="bench-profiler")
        profiler.start()
    import jax

    devs = jax.devices()
    report = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    write_json(device_path, report)
    from distributed_oracle_search_tpu.cli import process_query

    try:
        rc = process_query.main(argv)
    finally:
        if profiler is not None:
            signal.signal(signal.SIGUSR1, signal.SIG_IGN)
            signal.signal(signal.SIGUSR2, signal.SIG_IGN)
            if start.is_set():
                stop.set()
                profiler.join(timeout=300)
    write_json(device_path, {**report, "peak_bytes": [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()]})
    return rc


if __name__ == "__main__":
    sys.exit(main())
