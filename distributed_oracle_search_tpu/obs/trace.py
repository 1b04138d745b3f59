"""Nested span tracing with two sinks: the JAX profiler and Chrome
trace-event JSON.

The timing half of the observability layer (``obs/``): phases of a query
batch — head-side prepare/partition/send, the serving path's wait,
prep, walk, fetch and finish, the gateway's frame and reply —
run inside :func:`span` context managers. One span feeds up to two
sinks:

* **the profiler**: whenever JAX is already imported in the process and
  a profile is recording, the span is a ``jax.profiler.TraceAnnotation``
  — it lands on the host plane of the profile's ``.xplane.pb``
  (``/host:CPU``, one line per thread) with its arguments, on the same
  clock as the device's programs, so Perfetto or TensorBoard shows each
  batch's host spans above the device work they drove. :func:`span`
  never imports JAX itself: a JAX-free process (a benchmark client, a
  ``--backend host`` gateway) pays one dictionary lookup.
* **Chrome trace-event JSON** (``{"traceEvents": [...]}``, "X" complete
  events, loadable in Perfetto or ``chrome://tracing``) when collection
  is on: process-wide via :func:`enable` (``process_query --trace``) or
  for one thread via :func:`capture`.

Head and worker are separate processes in host mode, so Chrome spans
join across the FIFO wire via a **trace id**: the head stamps each
batch's ``RuntimeConfig.trace_id`` (a backward-compatible wire extension
— old servers filter the unknown key), the worker captures its spans for
that batch under the same id and materializes them as a
``<queryfile>.trace`` sidecar (the same shared-dir channel the
``.paths`` extension rides), and the head ingests the sidecars into one
merged trace file.

**Tags**: :func:`tagged` sets arguments (the serving frontend's shard-
local ``batch`` number and the batch ``size``) that every span the
thread opens inside the block carries, in both sinks, so the engine's
spans name the batch they served without knowing about batches.

Clock discipline (Chrome sink): event **timestamps** are epoch
microseconds (``time.time_ns``) so events from different processes land
on one timeline without negotiation; **durations** come from the
monotonic ``perf_counter_ns`` so a span is immune to wall-clock steps.

Cost discipline: with no profile recording and Chrome collection off,
:func:`span` returns one shared no-op context manager — no allocation,
no clock read. An annotation while a profile records costs about a
microsecond.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import uuid

_lock = threading.Lock()
_events: list[dict] = []
_enabled = False


class _ThreadState(threading.local):
    """Per-thread state. The class attributes are each thread's defaults,
    so a read never misses (a miss on a bare ``threading.local`` costs
    about half a microsecond)."""

    trace_id = None
    capture = None
    tags = None


_tls = _ThreadState()
#: ``jax.profiler.TraceAnnotation`` and its ``is_enabled`` (a profile is
#: recording), once JAX is imported in the process
_annotation_cls = None
_recording = None


def enable(on: bool = True) -> None:
    """Turn span collection on/off process-wide."""
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def set_trace_id(trace_id: str | None) -> None:
    """Set the current thread's trace id (stamped on every span it
    opens; explicit ``trace_id=`` span args override)."""
    _tls.trace_id = trace_id


def current_trace_id() -> str | None:
    return getattr(_tls, "trace_id", None)


def current_tags() -> dict:
    """The arguments :func:`tagged` set on this thread (empty outside
    any block)."""
    return getattr(_tls, "tags", None) or {}


class tagged:
    """Every span this thread opens inside the block carries ``tags``
    as arguments (explicit span arguments win). Blocks nest; the outer
    tags come back on exit."""

    __slots__ = ("tags", "_prev")

    def __init__(self, **tags):
        self.tags = tags

    def __enter__(self) -> "tagged":
        self._prev = getattr(_tls, "tags", None)
        _tls.tags = {**self._prev, **self.tags} if self._prev else self.tags
        return self

    def __exit__(self, *exc) -> bool:
        _tls.tags = self._prev
        return False


def _bind_profiler():
    """``TraceAnnotation.is_enabled`` once JAX is imported here, else
    None. Never imports JAX."""
    global _annotation_cls, _recording
    mod = sys.modules.get("jax._src.profiler")
    if mod is None:
        return None
    _annotation_cls = mod.TraceAnnotation
    _recording = _annotation_cls.is_enabled
    return _recording


class _NullSpan:
    """Shared do-nothing context manager: the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _emit(ev: dict) -> None:
    """Route a finished event: to the thread's capture buffer when one
    is open (per-request worker capture), else the global buffer."""
    buf = getattr(_tls, "capture", None)
    if buf is not None:
        buf.append(ev)
        return
    with _lock:
        _events.append(ev)


def _make_event(name: str, ts_us: int, dur_us: int, args: dict) -> dict:
    if "trace_id" not in args:
        tid = current_trace_id()
        if tid is not None:
            args = {**args, "trace_id": tid}
    return {
        "name": name,
        "ph": "X",
        "ts": ts_us,
        "dur": dur_us,
        "pid": os.getpid(),
        "tid": threading.get_ident() & 0x7FFFFFFF,
        "args": args,
    }


class _Span:
    """A Chrome event, and the profiler annotation when one records."""

    __slots__ = ("name", "args", "_ann", "_t0_wall_us", "_t0_perf")

    def __init__(self, name: str, args: dict, ann):
        self.name = name
        self.args = args
        self._ann = ann

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0_wall_us = time.time_ns() // 1000
        self._t0_perf = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter_ns() - self._t0_perf) // 1000
        _emit(_make_event(self.name, self._t0_wall_us, dur_us, self.args))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def span(name: str, **args):
    """Context manager timing one phase, in every sink that is on (see
    the module docstring). ``args``, after the thread's :func:`tagged`
    arguments, land in the event's arguments (``trace_id`` defaults to
    the thread's current id in Chrome events). Returns a shared no-op
    when no sink is on."""
    recording = _recording or _bind_profiler()
    profile = recording is not None and recording()
    chrome = _enabled or getattr(_tls, "capture", None) is not None
    if not (profile or chrome):
        return _NULL_SPAN
    tags = getattr(_tls, "tags", None)
    if tags:
        args = {**tags, **args}
    if not chrome:
        return _annotation_cls(name, **args)
    return _Span(name, args,
                 _annotation_cls(name, **args) if profile else None)


def events() -> list[dict]:
    with _lock:
        return list(_events)


def clear() -> None:
    with _lock:
        _events.clear()


def ingest(evs: list[dict]) -> None:
    """Merge externally collected events (e.g. a worker sidecar) into
    this process's buffer."""
    with _lock:
        _events.extend(evs)


class capture:
    """Divert the spans THIS THREAD opens during the ``with`` block into
    ``self.events`` (activating span collection for the thread if
    tracing was otherwise off).

    The worker server uses this per request: an incoming ``trace_id``
    turns collection on for exactly that batch, the captured events are
    stamped with the id and shipped back via the batch's sidecar — they
    deliberately bypass the global buffer, so an in-process server (test
    harnesses run head + workers in one process) never double-reports a
    span both directly and through the sidecar the head ingests.
    Captures nest per thread; other threads are unaffected.
    """

    def __init__(self, trace_id: str | None = None):
        self.trace_id = trace_id
        self.events: list[dict] = []

    def __enter__(self) -> "capture":
        self._prev_buf = getattr(_tls, "capture", None)
        _tls.capture = self.events
        if self.trace_id is not None:
            self._prev_tid = current_trace_id()
            set_trace_id(self.trace_id)
        return self

    def __exit__(self, *exc) -> bool:
        _tls.capture = self._prev_buf
        if self.trace_id is not None:
            set_trace_id(self._prev_tid)
        return False


# --------------------------------------------------------------- files

def trace_sidecar_for(queryfile: str) -> str:
    """Where a worker materializes a batch's span events for the head to
    collect (the ``.paths`` pattern: rides the shared dir, not the
    stats FIFO)."""
    return queryfile + ".trace"


def write_events(path: str, evs: list[dict]) -> None:
    """Atomic sidecar write: the head (or a fleet-aggregation pass)
    polls for sidecars over NFS and must never ingest a torn JSON list.
    Lazy import — ``utils.atomicio`` registers its own obs counters."""
    from ..utils.atomicio import atomic_write_bytes
    atomic_write_bytes(path, json.dumps(evs).encode())


def read_events(path: str) -> list[dict]:
    with open(path) as f:
        out = json.load(f)
    if not isinstance(out, list):
        raise ValueError(f"{path}: expected a JSON list of events")
    return out


def write_trace(path: str, extra_events: list[dict] | None = None) -> None:
    """Write the full Chrome trace-event file (buffered events plus any
    ``extra_events``), loadable in Perfetto / chrome://tracing."""
    evs = events()
    if extra_events:
        evs = evs + list(extra_events)
    from ..utils.atomicio import atomic_write_bytes
    atomic_write_bytes(path, json.dumps(
        {"traceEvents": evs, "displayTimeUnit": "ms"}, indent=1).encode())
