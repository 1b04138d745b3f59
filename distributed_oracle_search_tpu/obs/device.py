"""Per-compiled-program XLA cost/memory capture.

ROADMAP item 1 (the Pallas walk kernel) needs *measured* per-program
FLOPs, bytes-accessed, and HBM footprints before anyone can claim a
kernel closed the roofline gap — a wall-clock number alone cannot say
whether the walk is bandwidth-bound or issue-bound. This module records
XLA's own analyses for the programs the engine actually runs:

* :func:`analyze` — AOT-lower a jitted callable with concrete args and
  read ``cost_analysis()`` (FLOPs, bytes accessed) plus — after an AOT
  ``compile()`` — ``memory_analysis()`` (argument/output/temp bytes;
  their sum is the program's HBM footprint). Returns a plain dict, or
  None when the backend exposes neither (host CPU exposes costs but may
  return no memory stats; both absences degrade, never raise).
* :func:`capture` — :func:`analyze` + record under a program key.
  ``worker.engine`` calls it once per entry of its existing compiled-
  program cache (the ``_jit_seen`` keys), so a resident worker
  accumulates exactly one entry per distinct program, and the capture
  cost (one re-lower; the compile hits XLA's cache) is paid once,
  off the steady-state path.

The store exports three ways: :func:`snapshot` (JSON — ``bench.py``
embeds it in ``BENCH_DETAIL.json`` and derives achieved-vs-peak
gather-bandwidth rooflines), :func:`to_prometheus` (labeled gauges on
the ``/metrics`` scrape), and the ``device_programs_analyzed`` registry
gauge (the fleet aggregator's cheap cardinality signal).

``DOS_DEVICE_COSTS=0`` disables capture entirely (the engine then skips
even the key lookup).
"""

from __future__ import annotations

import os
import threading

from ..utils.env import env_flag
from ..utils.log import get_logger
from . import metrics as obs_metrics

log = get_logger(__name__)

G_PROGRAMS = obs_metrics.gauge(
    "device_programs_analyzed",
    "compiled programs with a captured XLA cost/memory analysis")

_COSTS: dict[str, dict] = {}
_lock = threading.Lock()

#: memory_analysis attributes summed into the HBM footprint
_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes")


def enabled() -> bool:
    return env_flag("DOS_DEVICE_COSTS", True)


def analyze(fn, *args, **kwargs) -> dict | None:
    """XLA cost + memory analysis of ``fn(*args, **kwargs)``.

    ``fn`` must be a ``jax.jit`` wrapper (it has ``.lower``); a bare
    callable is jitted first. Any failure — a backend refusing
    analysis, a donation mismatch — returns
    None with a debug log, never an exception into the serving path.
    """
    try:
        if not hasattr(fn, "lower"):
            import jax
            fn = jax.jit(fn)
        lowered = fn.lower(*args, **kwargs)
        out: dict = {}
        try:
            cost = lowered.cost_analysis()
            if cost:
                out["flops"] = float(cost.get("flops", 0.0))
                out["bytes_accessed"] = float(
                    cost.get("bytes accessed", 0.0))
        except Exception as e:  # noqa: BLE001 — degrade per analysis
            log.debug("cost_analysis unavailable: %s", e)
        try:
            mem = lowered.compile().memory_analysis()
            if mem is not None:
                for f in _MEM_FIELDS:
                    out[f.replace("_size_in_bytes", "_bytes")] = int(
                        getattr(mem, f, 0))
                out["hbm_bytes"] = sum(
                    int(getattr(mem, f, 0)) for f in _MEM_FIELDS)
                out["generated_code_bytes"] = int(
                    getattr(mem, "generated_code_size_in_bytes", 0))
        except Exception as e:  # noqa: BLE001
            log.debug("memory_analysis unavailable: %s", e)
        return out or None
    except Exception as e:  # noqa: BLE001 — capture is advisory
        log.debug("program analysis failed: %s", e)
        return None


def capture(key, fn, *args, **kwargs) -> dict | None:
    """Analyze once per ``key`` and record the result. Returns the
    stored entry (existing or new), or None when disabled/failed."""
    if not enabled():
        return None
    skey = key if isinstance(key, str) else repr(key)
    with _lock:
        if skey in _COSTS:
            return _COSTS[skey]
    entry = analyze(fn, *args, **kwargs)
    if entry is None:
        return None
    with _lock:
        _COSTS.setdefault(skey, entry)
        G_PROGRAMS.set(len(_COSTS))
        return _COSTS[skey]


def derive_bandwidth(entry: dict | None, seconds: float,
                     peak_gbps: float | None) -> dict | None:
    """Fold a measured wall-clock into a captured analysis: achieved
    GB/s off ``bytes_accessed`` plus the utilization fraction against a
    calibrated HBM peak. The roofline arithmetic the bench used to
    inline for the XLA walk, shared here so the fused Pallas kernel's
    capture derives the SAME figures (kernel-vs-kernel comparisons must
    not differ in the denominator math). Mutates and returns ``entry``;
    None in (no analysis / no timing) degrades to None out."""
    if not entry or seconds <= 0 or "bytes_accessed" not in entry:
        return entry
    gbps = entry["bytes_accessed"] / seconds / 1e9
    entry["achieved_gbps"] = round(gbps, 2)
    if peak_gbps and peak_gbps > 0:
        entry["hbm_bw_utilization"] = round(gbps / peak_gbps, 4)
    return entry


def record(key, entry: dict) -> None:
    """Store an externally computed analysis under ``key`` (bench uses
    this for programs it lowers itself)."""
    with _lock:
        _COSTS[key if isinstance(key, str) else repr(key)] = dict(entry)
        G_PROGRAMS.set(len(_COSTS))


def snapshot() -> dict:
    """``{program_key: {flops, bytes_accessed, hbm_bytes, ...}}``."""
    with _lock:
        return {k: dict(v) for k, v in sorted(_COSTS.items())}


def to_prometheus() -> str:
    """Labeled per-program gauges for the scrape endpoint."""
    with _lock:
        costs = {k: dict(v) for k, v in sorted(_COSTS.items())}
    if not costs:
        return ""
    lines = []
    for field, help_ in (
            ("flops", "XLA cost_analysis FLOPs per program execution"),
            ("bytes_accessed", "XLA cost_analysis bytes accessed"),
            ("hbm_bytes", "argument+output+temp device memory")):
        samples = [(k, v[field]) for k, v in costs.items()
                   if field in v]
        if not samples:
            continue
        lines.append(f"# TYPE device_program_{field} gauge")
        lines.append(f"# HELP device_program_{field} {help_}")
        for key, val in samples:
            esc = key.replace("\\", "\\\\").replace('"', '\\"')
            lines.append(
                f'device_program_{field}{{program="{esc}"}} {val:.10g}')
    return "\n".join(lines) + ("\n" if lines else "")


def device_status() -> dict:
    """The process's accelerator as JAX reports it, plus the memory
    counters of its first device where the backend keeps them (the
    ``device`` section of a server's ``/statusz``)."""
    import jax

    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            **{k: int(stats[k]) for k in
               ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
               if k in stats}}


def reset() -> None:
    """Drop every captured program (tests only)."""
    with _lock:
        _COSTS.clear()
        G_PROGRAMS.set(0)
