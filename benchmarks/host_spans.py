"""The chip's idle time, named by the host spans open during it.

    python3 benchmarks/host_spans.py <trace dir> [<lo_ns> <hi_ns>]

The program's stage spans (``obs.trace.span``) are profiler annotations
on the trace's host plane (``/host:CPU``, one line per thread), in
nanoseconds after the profile's start like the device planes that
``trace_reduce`` reads. The chip is idle in the complement of the union
of its operations; each idle interval is intersected with the spans of
each name. The runner thread's leaf spans (``RUNNER_LEAVES``) follow one
another, so they say what the thread that feeds the chip did while the
chip waited; idle time under none of them is "none open".

Without a window the span of the device's events is used; the
benchmark's reader re-reduces the run's own window.
"""

from __future__ import annotations

import glob
import json
import os
import sys

import trace_reduce

HOST_PLANE = "/host:CPU"
#: the runner thread's stage spans that hold no other stage span
RUNNER_LEAVES = ("serve.wait", "worker.prep", "worker.walk",
                 "worker.fetch", "serve.finish")
#: the program's span names (leaves and the spans around them)
PREFIXES = ("serve.", "worker.", "gateway.")


def merged(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering ``intervals``."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted, disjoint lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(dev: trace_reduce.Device, window: tuple) -> list:
    """The window less the union of the device's operations."""
    busy = merged((s, e) for _, s, e in dev.ops or dev.modules)
    out, cur = [], window[0]
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, window[1])))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return [(s, e) for s, e in out if e > s]


def host_spans(planes, window: tuple) -> dict:
    """``{name: [(start, end), ...]}`` of the program's spans on the
    host plane, clipped to ``window``."""
    out: dict = {}
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIXES):
                    continue
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                s, e = max(s, window[0]), min(e, window[1])
                if e > s:
                    out.setdefault(ev.name, []).append((s, e))
    return out


def attribute(planes, trace: trace_reduce.Trace) -> dict | None:
    """Idle nanoseconds over the devices of ``trace``, split by the span
    names open during them; None when the host plane holds none of the
    runner's leaf spans (a program without them)."""
    spans = host_spans(planes, trace.window)
    if not any(name in spans for name in RUNNER_LEAVES):
        return None
    union = {name: merged(iv) for name, iv in spans.items()}
    leaves = merged(iv for name in RUNNER_LEAVES
                    for iv in spans.get(name, ()))
    idle = attributed = 0.0
    by_span = {name: 0.0 for name in union}
    for dev in trace.devices:
        gaps = idle_intervals(dev, trace.window)
        idle += sum(e - s for s, e in gaps)
        attributed += overlap_ns(gaps, leaves)
        for name, iv in union.items():
            by_span[name] += overlap_ns(gaps, iv)
    return {"idle_ns": idle, "attributed_ns": attributed,
            "none_open_ns": idle - attributed, "by_span_ns": by_span,
            "batches": len(spans.get("worker.walk", ())),
            "devices": len(trace.devices)}


def load_planes(path: str) -> list:
    """The planes of an ``.xplane.pb`` as a list: ``ProfileData.planes``
    is an iterator that a second pass finds empty."""
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)


def newest(pattern: str) -> str | None:
    paths = sorted(glob.glob(pattern, recursive=True),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def for_run(run: dict, work: str) -> dict | None:
    """The attribution of a traced serve run: the newest ``.xplane.pb``
    under ``<work>/runs/*/trace``, taken as the run's own only if its
    device planes reduce over the run's window to the same busy time;
    None otherwise."""
    tr = run.get("trace")
    if tr is None or not tr.devices:
        return None
    path = newest(os.path.join(work, "runs", "*", "trace", "**",
                               "*.xplane.pb"))
    if path is None:
        return None
    planes = load_planes(path)
    again = trace_reduce.read_planes(planes, 0.0, tr.window)
    if len(again.devices) != len(tr.devices) or any(
            abs(trace_reduce.busy_s(a) - trace_reduce.busy_s(b)) > 1e-9
            for a, b in zip(again.devices, tr.devices)):
        return None
    return attribute(planes, again)


def table(att: dict) -> dict:
    """The attribution as seconds, shares of the idle time and
    milliseconds per batch, leaf spans first."""
    idle = att["idle_ns"] or 1.0
    per = max(att["batches"], 1) * max(att["devices"], 1)

    def row(ns):
        return {"s": ns / 1e9, "pct_of_idle": 100.0 * ns / idle,
                "ms_per_batch": ns / 1e6 / per}

    leaves = {n: row(att["by_span_ns"].get(n, 0.0)) for n in RUNNER_LEAVES}
    leaves["none open"] = row(att["none_open_ns"])
    others = {n: row(v) for n, v in sorted(att["by_span_ns"].items())
              if n not in RUNNER_LEAVES}
    return {"idle_s": att["idle_ns"] / 1e9, "batches": att["batches"],
            "attributed_pct": 100.0 * att["attributed_ns"] / idle,
            "leaf_spans": leaves, "other_spans_open": others}


def main(argv) -> int:
    path = newest(os.path.join(argv[0], "**", "*.xplane.pb"))
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    planes = load_planes(path)
    if len(argv) >= 3:
        tr = trace_reduce.read_planes(planes, 0.0,
                                      (float(argv[1]), float(argv[2])))
    else:
        tr = trace_reduce.read_planes(planes, None, None)
    att = attribute(planes, tr)
    if att is None:
        print("no runner stage spans on the host plane", file=sys.stderr)
        return 1
    print(json.dumps({"file": path, **table(att)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
