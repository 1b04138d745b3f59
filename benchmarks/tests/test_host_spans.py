"""CPU tests of ``host_spans``: the chip's idle time attributed to the
host spans open during it, on fake planes (ProfileData-like objects)
and on a small annotated trace recorded on the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import harness  # noqa: E402
import host_spans  # noqa: E402
import trace_reduce  # noqa: E402

ANNOTATED = os.path.join(os.path.dirname(__file__), "data",
                         "annotated.xplane.pb")


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _planes(host_events):
    """A chip busy in [0, 100), [300, 400) and [700, 800), and a host
    plane holding ``host_events`` on one thread."""
    ops = SimpleNamespace(name=trace_reduce.OPS_LINE, events=[
        _ev("fusion", 0, 100), _ev("fusion", 300, 100),
        _ev("fusion", 700, 100)])
    tpu = SimpleNamespace(name="/device:TPU:0", lines=[ops])
    runner = SimpleNamespace(name="python3", events=host_events)
    host = SimpleNamespace(name=host_spans.HOST_PLANE, lines=[runner])
    return [host, tpu]


#: idle [100, 300) under one span, [400, 700) split between two,
#: [800, 1000) under none of the runner's
HOST = [_ev("worker.walk", 0, 100), _ev("worker.fetch", 100, 200),
        _ev("worker.walk", 300, 100), _ev("serve.finish", 400, 150),
        _ev("serve.wait", 550, 150), _ev("worker.walk", 700, 100),
        _ev("gateway.frame", 0, 1000), _ev("PjitFunction(f)", 800, 50)]


def _trace(planes, window=(0.0, 1000.0)):
    return trace_reduce.read_planes(planes, 0.0, window)


def test_idle_split_by_the_spans_open_during_it():
    planes = _planes(HOST)
    att = host_spans.attribute(planes, _trace(planes))
    assert att["idle_ns"] == 700
    # one gap under one span, one split between two, one under none
    by = att["by_span_ns"]
    assert by["worker.fetch"] == 200
    assert (by["serve.finish"], by["serve.wait"]) == (150, 150)
    assert by["worker.walk"] == 0
    assert att["attributed_ns"] == 500 and att["none_open_ns"] == 200
    # a span around the runner's (the gateway's) names idle time but
    # is no runner leaf; the runtime's own events are left out
    assert by["gateway.frame"] == 700
    assert "PjitFunction(f)" not in by
    assert att["batches"] == 3
    tab = host_spans.table(att)
    assert tab["attributed_pct"] == pytest.approx(500 / 7)
    assert tab["leaf_spans"]["none open"]["ms_per_batch"] == \
        pytest.approx(200 / 1e6 / 3)


def test_window_clips_spans_and_gaps():
    planes = _planes(HOST)
    att = host_spans.attribute(planes, _trace(planes, (200.0, 600.0)))
    # idle [200, 300) and [400, 600)
    assert att["idle_ns"] == 300
    assert att["by_span_ns"]["worker.fetch"] == 100
    assert att["by_span_ns"]["serve.wait"] == 50
    assert att["none_open_ns"] == 0


def test_no_runner_spans_reads_nothing():
    """A program without the stage spans (the parent of the change that
    added them) gives no attribution, and the reader no number."""
    planes = _planes([_ev("gateway.frame", 0, 1000)])
    assert host_spans.attribute(planes, _trace(planes)) is None


def test_reader_takes_only_the_runs_own_trace(tmp_path, monkeypatch):
    path = tmp_path / "runs" / "cell" / "trace" / "p" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    planes = _planes(HOST)
    monkeypatch.setattr(host_spans, "load_planes", lambda p: planes)
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    reader = harness.load_reader("idle_attributed_pct.serve")
    run = {"kind": "serve", "trace": _trace(planes)}
    assert reader.read(run) == pytest.approx(500 / 7)
    # a trace whose busy time over the window differs is another run's
    other = _planes(HOST)
    other[1].lines[0].events.append(_ev("fusion", 900, 50))
    assert reader.read({"kind": "serve", "trace": _trace(other)}) is None
    assert reader.read({"kind": "serve", "trace": None}) is None
    assert reader.read({"kind": "build", "trace": _trace(planes)}) is None
    monkeypatch.setattr(host_spans, "load_planes", lambda p: _planes(
        [_ev("gateway.frame", 0, 1000)]))
    assert reader.read(run) is None


@pytest.mark.skipif(not os.path.exists(ANNOTATED),
                    reason="no recorded annotated chip trace")
def test_recorded_annotated_chip_trace():
    """A trace recorded on the chip (``record_annotated_trace.py``): the
    stage spans lie on the host plane around the device programs they
    launched, and the idle time between programs falls under the stage
    spans, or under none between batches. On the chip the device's
    clock and the host's differ by a constant offset of about a
    millisecond: each execution starts that long before its walk span
    on the host clock."""
    planes = host_spans.load_planes(ANNOTATED)
    stats = {k: float(v) for p in planes for k, v in p.stats
             if k in ("profile_start_time", "profile_stop_time")}
    window = (0.0, stats["profile_stop_time"] - stats["profile_start_time"])
    tr = trace_reduce.read_planes(planes, 0.0, window)
    assert tr.devices and tr.devices[0].name.startswith("/device:TPU:")
    runs = sorted((s, e) for n, s, e in tr.devices[0].modules
                  if "bench_annotated_step" in n)
    walks = sorted(host_spans.host_spans(planes, window)["worker.walk"])
    assert len(runs) == len(walks) == 4
    offsets = sorted(ws - s for (s, _), (ws, _) in zip(runs, walks))
    offset = offsets[len(offsets) // 2]
    assert abs(offset) < 3e6 and offsets[-1] - offsets[0] < 0.5e6
    for (s, e), (ws, we) in zip(runs, walks):
        # on the host's clock each execution lies in its walk span
        assert ws - 0.5e6 <= s + offset and e + offset <= we
    att = host_spans.attribute(planes, tr)
    assert att["batches"] == 4
    by = att["by_span_ns"]
    for name in ("serve.wait", "worker.prep", "serve.finish"):
        assert by[name] > 1e6            # each holds a sleep of 2-4 ms
    assert att["none_open_ns"] > 4 * 5e6  # the 5 ms between batches
    assert att["attributed_ns"] + att["none_open_ns"] == \
        pytest.approx(att["idle_ns"])
