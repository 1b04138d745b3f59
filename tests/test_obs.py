"""Observability layer: metrics registry, span tracing, worker-id logs,
server failure-path counters, and the end-to-end traced campaign.

The integration test at the bottom is the acceptance gate for the obs
subsystem: a host-mode campaign through real ``FifoServer`` instances
with ``--trace`` and ``--metrics-dump`` set must produce a Chrome trace
whose head-side and worker-side spans share a ``trace_id``, and a
metrics snapshot carrying the serve-loop health counters and per-phase
histograms.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.obs import trace as obs_trace
from distributed_oracle_search_tpu.obs.metrics import MetricsRegistry
from distributed_oracle_search_tpu.transport.wire import RuntimeConfig
from distributed_oracle_search_tpu.utils.log import (
    get_logger, set_verbosity, set_worker_id,
)
from distributed_oracle_search_tpu.utils.timer import Timer


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Tracing is process-global: leave it as we found it."""
    yield
    obs_trace.enable(False)
    obs_trace.clear()
    obs_trace.set_trace_id(None)


# ------------------------------------------------------------------ metrics

def test_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    reg.counter("c_total").inc(4)
    reg.gauge("g").set(2.5)
    reg.histogram("h_seconds").observe(0.005)
    reg.histogram("h_seconds").observe(2.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c_total"] == 5
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h_seconds"]
    assert h["count"] == 2 and abs(h["sum"] - 2.005) < 1e-9
    # buckets are cumulative (Prometheus semantics)
    assert h["buckets"]["0.01"] == 1
    assert h["buckets"]["5.0"] == 2


def test_histogram_overflow_lands_in_inf_only():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(0.1, 1.0))
    h.observe(50.0)
    d = h.as_dict()
    assert d["count"] == 1 and all(v == 0 for v in d["buckets"].values())


def test_registry_reset_zeroes_in_place_keeping_handles():
    """reset() must not orphan handles held from import time: after a
    reset, existing Counter/Histogram objects keep feeding snapshots."""
    reg = MetricsRegistry()
    c = reg.counter("kept_total")
    h = reg.histogram("kept_seconds")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"]["kept_total"] == 0
    assert snap["histograms"]["kept_seconds"]["count"] == 0
    c.inc()                     # the ORIGINAL handle, post-reset
    h.observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["kept_total"] == 1
    assert snap["histograms"]["kept_seconds"]["count"] == 1


def test_registry_get_or_create_is_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("frames_total", help="frames").inc(3)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    text = reg.to_prometheus()
    assert "# TYPE frames_total counter" in text
    assert "frames_total 3" in text
    assert '# HELP frames_total frames' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text


def test_registry_dump_json_is_valid(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    path = str(tmp_path / "snap.json")
    reg.dump_json(path)
    snap = json.load(open(path))
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c"] == 1


def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in
                                                range(1000)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


# -------------------------------------------------------------------- trace

def test_span_disabled_is_shared_noop():
    """Chrome collection off and no profile recording: one shared null
    object, no allocation, nothing recorded — tags included."""
    assert not obs_trace.enabled()
    s1 = obs_trace.span("a", k=1)
    s2 = obs_trace.span("b")
    with obs_trace.tagged(batch=3, size=2):
        s3 = obs_trace.span("c")
    assert s1 is s2 is s3           # one shared null object, no allocs
    with s1:
        pass
    assert obs_trace.events() == []


def test_span_records_chrome_events_with_trace_id():
    obs_trace.enable()
    obs_trace.set_trace_id("tid-1")
    with obs_trace.span("outer", wid=3):
        with obs_trace.span("inner"):
            time.sleep(0.002)
    with obs_trace.tagged(batch=7, size=2):
        with obs_trace.span("tagged", wid=3, size=5):
            time.sleep(0.001)
    evs = obs_trace.events()
    assert [e["name"] for e in evs] == ["inner", "outer", "tagged"]
    for e in evs:
        assert e["ph"] == "X" and e["pid"] == os.getpid()
        assert e["args"]["trace_id"] == "tid-1"
    inner, outer, tagged = evs
    assert inner["dur"] >= 2000          # us
    assert outer["dur"] >= inner["dur"]
    # the thread's tags ride along; an explicit argument wins
    assert tagged["dur"] >= 1000
    assert (tagged["args"]["batch"], tagged["args"]["size"],
            tagged["args"]["wid"]) == (7, 5, 3)
    # explicit trace_id overrides the thread's
    with obs_trace.span("explicit", trace_id="other"):
        pass
    assert obs_trace.events()[-1]["args"]["trace_id"] == "other"


def test_capture_diverts_this_threads_spans():
    with obs_trace.capture("batch-7") as cap:
        with obs_trace.span("worker.search"):
            pass
    assert len(cap.events) == 1
    assert cap.events[0]["args"]["trace_id"] == "batch-7"
    # nothing leaked to the global buffer, and tracing stayed off
    assert obs_trace.events() == []
    assert not obs_trace.enabled()
    assert obs_trace.current_trace_id() is None


def test_capture_does_not_steal_other_threads_events():
    obs_trace.enable()
    release = threading.Event()
    started = threading.Event()

    def other():
        started.wait(5)
        with obs_trace.span("other.thread"):
            pass
        release.set()

    th = threading.Thread(target=other)
    th.start()
    with obs_trace.capture("mine") as cap:
        started.set()
        release.wait(5)
        with obs_trace.span("mine.span"):
            pass
    th.join()
    assert [e["name"] for e in cap.events] == ["mine.span"]
    assert [e["name"] for e in obs_trace.events()] == ["other.thread"]


def test_write_trace_and_sidecar_roundtrip(tmp_path):
    obs_trace.enable()
    with obs_trace.span("head.send", trace_id="t"):
        pass
    sidecar = str(tmp_path / "q.trace")
    obs_trace.write_events(sidecar, [{"name": "worker.search", "ph": "X",
                                      "ts": 1, "dur": 2, "pid": 9,
                                      "tid": 9, "args": {"trace_id": "t"}}])
    obs_trace.ingest(obs_trace.read_events(sidecar))
    out = str(tmp_path / "trace.json")
    obs_trace.write_trace(out)
    doc = json.load(open(out))
    names = {e["name"] for e in doc["traceEvents"]}
    assert names == {"head.send", "worker.search"}


def test_trace_sidecar_path_convention():
    assert obs_trace.trace_sidecar_for("/nfs/query.host0") == \
        "/nfs/query.host0.trace"


# -------------------------------------------------------------------- timer

def test_timer_elapsed_works_mid_block():
    with Timer() as t:
        assert t.interval == 0.0          # documented mid-block reading
        time.sleep(0.02)
        mid = t.elapsed
        assert mid >= 0.015
    assert t.interval >= mid              # exit keeps interval semantics
    assert t.elapsed == t.interval        # after exit they agree


def test_timer_elapsed_before_any_block():
    t = Timer(1.5)
    assert t.elapsed == 1.5


# ------------------------------------------------------------------ logging

def test_log_records_carry_worker_id():
    set_verbosity(1)
    root = get_logger()
    records = []

    class Sink(logging.Handler):
        def emit(self, record):
            records.append(self.format(record))

    sink = Sink()
    sink.setFormatter(root.handlers[0].formatter)
    for f in root.handlers[0].filters:
        sink.addFilter(f)
    root.addHandler(sink)
    try:
        log = get_logger("worker.test")
        set_worker_id(3)
        log.info("from the worker")
        set_worker_id(None)
        log.info("from the head")
        in_thread = []

        def other():
            set_worker_id(5)
            log.info("thread-local")
            in_thread.append(True)
        th = threading.Thread(target=other)
        th.start()
        th.join()
    finally:
        root.removeHandler(sink)
        set_verbosity(0)
    assert "[w3]" in records[0]
    assert "[w-]" in records[1]
    assert "[w5]" in records[2] and in_thread


# ------------------------------------------- server failure-path counters

from distributed_oracle_search_tpu.worker import server as server_mod
from distributed_oracle_search_tpu.worker.server import FifoServer


def _bare_server(tmp_path, name, frame_timeout=0.3):
    """A FifoServer with no engine/index: enough for every failure path
    (only a successfully decoded request ever touches the engine)."""
    s = FifoServer.__new__(FifoServer)
    s.wid = 0
    s.command_fifo = str(tmp_path / f"{name}.fifo")
    s.FRAME_TIMEOUT_S = frame_timeout
    return s


def _serve(server):
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    for _ in range(100):
        if os.path.exists(server.command_fifo):
            break
        time.sleep(0.02)
    else:
        pytest.fail("server fifo never appeared")
    return th


def _counters():
    return {k: v.value for k, v in [
        ("frames", server_mod.M_FRAMES),
        ("malformed", server_mod.M_MALFORMED),
        ("half", server_mod.M_HALF),
        ("dropped", server_mod.M_DROPPED),
        ("replies", server_mod.M_REPLIES),
    ]}


def test_server_counts_malformed_stray_line(tmp_path):
    s = _bare_server(tmp_path, "stray")
    answer = str(tmp_path / "stray.answer")
    os.mkfifo(answer)
    before = _counters()
    th = _serve(s)
    try:
        with open(s.command_fifo, "w") as f:
            f.write(f"this is not a frame {answer} -\n")
        with open(answer) as f:           # server FAILs the named fifo
            assert f.readline().strip() == "FAIL"
    finally:
        server_mod.stop_server(s.command_fifo)
        th.join(timeout=10)
    after = _counters()
    assert after["frames"] == before["frames"] + 1
    assert after["malformed"] == before["malformed"] + 1


def test_server_counts_undecodable_request(tmp_path):
    s = _bare_server(tmp_path, "badreq")
    answer = str(tmp_path / "badreq.answer")
    os.mkfifo(answer)
    before = _counters()
    th = _serve(s)
    try:
        # valid JSON config line, but line 2 has 2 tokens instead of 3
        with open(s.command_fifo, "w") as f:
            f.write('{"itrs": 1}\n' + f"queryfile {answer}\n")
        with open(answer) as f:
            assert f.readline().strip() == "FAIL"
    finally:
        server_mod.stop_server(s.command_fifo)
        th.join(timeout=10)
    after = _counters()
    assert after["malformed"] == before["malformed"] + 1


def test_server_counts_config_only_half_frame(tmp_path):
    s = _bare_server(tmp_path, "cfgonly")
    before = _counters()
    th = _serve(s)
    try:
        # two consecutive config lines: the second is pushed back as the
        # next frame's start, the first counts as a half frame; the stop
        # token then pairs with the pushed-back line and still wins
        with open(s.command_fifo, "w") as f:
            f.write('{"itrs": 1}\n{"itrs": 2}\n')
        time.sleep(0.2)
    finally:
        server_mod.stop_server(s.command_fifo)
        th.join(timeout=10)
    after = _counters()
    assert after["half"] == before["half"] + 1


def test_server_counts_timed_out_half_frame(tmp_path):
    s = _bare_server(tmp_path, "halftime", frame_timeout=0.15)
    before = _counters()
    th = _serve(s)
    try:
        with open(s.command_fifo, "w") as f:
            f.write('{"itrs": 1}\n')      # line 2 never arrives
        time.sleep(0.5)                   # > FRAME_TIMEOUT_S
    finally:
        server_mod.stop_server(s.command_fifo)
        th.join(timeout=10)
    after = _counters()
    assert after["half"] == before["half"] + 1


def test_server_counts_dropped_reply_when_reader_never_opens(tmp_path):
    s = _bare_server(tmp_path, "drop")
    fifo = str(tmp_path / "nobody-reads.fifo")
    os.mkfifo(fifo)
    before = _counters()
    s._reply(fifo, "1,2\n", deadline_s=0.15)      # no reader -> dropped
    after = _counters()
    assert after["dropped"] == before["dropped"] + 1
    assert after["replies"] == before["replies"]


def test_server_reply_wait_histogram_on_success(tmp_path):
    s = _bare_server(tmp_path, "ok")
    fifo = str(tmp_path / "read.fifo")
    os.mkfifo(fifo)
    got = []

    def reader():
        with open(fifo) as f:
            got.append(f.readline())
    th = threading.Thread(target=reader)
    th.start()
    before_count = server_mod.M_REPLY_WAIT.count
    before = _counters()
    s._reply(fifo, "payload\n", deadline_s=5.0)
    th.join(timeout=5)
    assert got == ["payload\n"]
    assert server_mod.M_REPLY_WAIT.count == before_count + 1
    assert _counters()["replies"] == before["replies"] + 1


# ----------------------------------------------- wire compat (trace_id)

def test_runtime_config_trace_id_roundtrip_and_old_peer_compat():
    rc = RuntimeConfig(trace_id="abc123/w0.d0")
    # new peer: preserved through the wire
    assert RuntimeConfig.from_json(rc.to_json()).trace_id == "abc123/w0.d0"
    # old-schema peer line (no trace_id key): default applies
    old = json.loads(rc.to_json())
    del old["trace_id"]
    assert RuntimeConfig.from_json(json.dumps(old)).trace_id == ""
    # symmetric: an old peer's from_json filter would drop the key, and
    # OUR filter drops keys from a future schema without complaint
    future = dict(json.loads(rc.to_json()), some_future_knob=7)
    back = RuntimeConfig.from_json(json.dumps(future))
    assert back.trace_id == "abc123/w0.d0"


# ------------------------------------------------- end-to-end integration

@pytest.fixture(scope="module")
def obs_cluster(tmp_path_factory):
    """Small built index + host conf (the test_drivers pattern, sized
    down: the obs integration test needs a real FIFO campaign, not a
    big one)."""
    from distributed_oracle_search_tpu.data import (
        Graph, ensure_synth_dataset,
    )
    from distributed_oracle_search_tpu.models.cpd import (
        write_index_manifest,
    )
    from distributed_oracle_search_tpu.parallel.partition import (
        DistributionController,
    )
    from distributed_oracle_search_tpu.utils.config import ClusterConfig
    from distributed_oracle_search_tpu.worker.build import main as build_main

    datadir = str(tmp_path_factory.mktemp("obsdata"))
    paths = ensure_synth_dataset(datadir, width=8, height=6, n_queries=48,
                                 seed=5)
    conf = ClusterConfig(
        workers=["localhost", "localhost"],
        partmethod="mod", partkey=2,
        outdir=os.path.join(datadir, "index"),
        xy_file=paths["xy"], scenfile=paths["scen"],
        diffs=["-", paths["diff"]],
        nfs=datadir,
    ).validate()
    for wid in range(conf.maxworker):
        build_main(["--input", conf.xy_file, "--partmethod",
                    conf.partmethod, "--partkey", str(conf.partkey),
                    "--workerid", str(wid),
                    "--maxworker", str(conf.maxworker),
                    "--outdir", conf.outdir])
    g = Graph.from_xy(conf.xy_file)
    dc = DistributionController(conf.partmethod, conf.partkey,
                                conf.maxworker, g.n)
    write_index_manifest(conf.outdir, dc)
    conf_path = os.path.join(datadir, "conf.json")
    conf.save(conf_path)
    return conf, conf_path


def test_engine_jit_split_keys_on_program_shape(obs_cluster):
    """The compile/steady split must key on the compiled program's
    shape: under a time budget the chunked table-search path reuses one
    chunk-wide program across batch sizes, so a bigger qpad alone must
    NOT book a steady-state batch as a compile."""
    from distributed_oracle_search_tpu.data import Graph, read_scen
    from distributed_oracle_search_tpu.parallel.partition import (
        DistributionController,
    )
    from distributed_oracle_search_tpu.worker.engine import (
        M_JIT, M_SEARCH, ShardEngine,
    )

    conf, _ = obs_cluster
    g = Graph.from_xy(conf.xy_file)
    dc = DistributionController("mod", 2, 2, g.n)
    eng = ShardEngine(g, dc, 0, conf.outdir)
    eng.astar_chunk = 4
    queries = read_scen(conf.scenfile)
    mine = queries[dc.worker_of(queries[:, 1]) == 0]
    assert len(mine) >= 12
    rc = RuntimeConfig(time=10**12)       # deadline set, never binding
    j0, s0 = M_JIT.count, M_SEARCH.count
    eng.answer(mine[:6], rc)    # qpad 8 > chunk 4: chunked, compiles
    eng.answer(mine[:12], rc)   # qpad 16: same chunk-wide program
    assert M_JIT.count - j0 == 1
    assert M_SEARCH.count - s0 == 1
    # astar never consumes k_moves (reference args.py:28): a new value
    # on a resident server is NOT a recompile
    eng_a = ShardEngine(g, dc, 0, conf.outdir, alg="astar")
    eng_a.astar_chunk = 4
    j0, s0 = M_JIT.count, M_SEARCH.count
    eng_a.answer(mine[:6], RuntimeConfig(k_moves=-1))
    eng_a.answer(mine[:6], RuntimeConfig(k_moves=8))
    assert M_JIT.count - j0 == 1
    assert M_SEARCH.count - s0 == 1


def test_traced_campaign_end_to_end(obs_cluster, tmp_path, monkeypatch):
    """--trace + --metrics-dump through a real FifoServer campaign:
    merged trace joins head and worker spans on one trace_id; the
    snapshot carries the health counters and phase histograms; the
    artifact dir gains obs_metrics.json next to parts.csv."""
    from distributed_oracle_search_tpu.cli import process_query as pq
    from distributed_oracle_search_tpu.worker import (
        FifoServer, stop_server,
    )

    conf, conf_path = obs_cluster
    fifos = {wid: str(tmp_path / f"worker{wid}.fifo")
             for wid in range(conf.maxworker)}
    monkeypatch.setattr(pq, "command_fifo_path", lambda wid: fifos[wid])
    servers = [FifoServer(conf, wid, command_fifo=fifos[wid])
               for wid in range(conf.maxworker)]
    threads = [threading.Thread(target=s.serve_forever, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    trace_path = str(tmp_path / "campaign.trace.json")
    dump_path = str(tmp_path / "metrics.json")
    outdir = str(tmp_path / "artifacts")
    before_frames = server_mod.M_FRAMES.value
    try:
        rc = pq.main(["-c", conf_path, "--backend", "host",
                      "--trace", trace_path, "--metrics-dump", dump_path,
                      "-o", outdir])
        assert rc == 0
    finally:
        for wid in fifos:
            try:
                stop_server(fifos[wid])
            except OSError:
                pass
        for t in threads:
            t.join(timeout=10)

    # (a) the merged Chrome trace: head + worker spans, joined on one id
    doc = json.load(open(trace_path))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"head.read", "head.partition", "head.prepare", "head.send",
            "worker.receive", "worker.prep", "worker.weights",
            "worker.walk", "worker.fetch"} <= names
    sends = {e["args"]["trace_id"]: e for e in evs
             if e["name"] == "head.send"}
    searches = {e["args"]["trace_id"]: e for e in evs
                if e["name"] == "worker.walk"}
    shared = set(sends) & set(searches)
    # every batch (2 workers x 2 diff rounds) joined head<->worker
    assert len(shared) == conf.maxworker * len(conf.diffs)
    for tid in shared:
        # the worker's search happened INSIDE the head's send window
        assert sends[tid]["ts"] <= searches[tid]["ts"]

    # (b) the metrics snapshot: health counters + phase histograms
    snap = json.load(open(dump_path))
    assert set(snap) == {"counters", "gauges", "histograms"}
    c, h = snap["counters"], snap["histograms"]
    assert c["server_frames_received_total"] - before_frames >= 4
    # failure-path counters are PRESENT (zero here) — dashboards can
    # alert on them without waiting for the first failure
    assert "server_frames_malformed_total" in c
    assert "server_replies_dropped_total" in c
    for name in ("worker_receive_seconds", "worker_weights_load_seconds",
                 "head_prepare_seconds", "head_send_seconds",
                 "server_reply_open_wait_seconds"):
        assert h[name]["count"] > 0, name
    # compile time split from steady state: first call per program key
    # landed in the jit histogram
    assert h["worker_jit_compile_seconds"]["count"] > 0

    # (c) snapshot also written next to the stats CSV
    side = json.load(open(os.path.join(outdir, "obs_metrics.json")))
    assert set(side) == {"counters", "gauges", "histograms"}
    assert os.path.exists(os.path.join(outdir, "parts.csv"))
