"""Live observability plane: sliding-window quantiles + exemplars, the
scrape endpoints against a live frontend, fleet snapshot/trace merging,
per-worker Prometheus labels, trace ids in log records, XLA program-cost
capture, and the bench-diff regression gate.

The endpoint round-trip test is the acceptance gate for the plane: a
running frontend with ``--obs-port``-style wiring must answer
``/metrics`` with live p50/p95/p99 gauges that move under load, and
``/statusz`` must report breaker + queue + replica state.
"""

import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_oracle_search_tpu.obs import device as obs_device
from distributed_oracle_search_tpu.obs import fleet as obs_fleet
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.obs import quantiles as obs_quantiles
from distributed_oracle_search_tpu.obs import trace as obs_trace
from distributed_oracle_search_tpu.obs.http import (
    ObsServer, resolve_obs_port, start_obs_server,
)
from distributed_oracle_search_tpu.obs.quantiles import (
    QuantileWindows, SlidingQuantiles,
)
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.serving import (
    CallableDispatcher, ServeConfig, ServingFrontend,
)
from distributed_oracle_search_tpu.transport import resilience
from distributed_oracle_search_tpu.utils.log import (
    get_logger, set_verbosity, set_worker_id,
)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs_trace.enable(False)
    obs_trace.clear()
    obs_trace.set_trace_id(None)


# ------------------------------------------------------ quantile windows

def _nearest_rank(data, q):
    data = sorted(data)
    import math
    return data[max(0, min(len(data) - 1, math.ceil(q * len(data)) - 1))]


def test_window_quantiles_match_sorted_reference():
    w = SlidingQuantiles(window_s=60, buckets=6, max_samples=10_000)
    rng = np.random.default_rng(3)
    vals = rng.gamma(2.0, 0.01, size=2000).tolist()
    for v in vals:
        w.observe(v, now=100.0)
    qs = w.quantiles(now=100.0)
    for q in (0.5, 0.95, 0.99):
        assert qs[q] == pytest.approx(_nearest_rank(vals, q))
    assert w.count(now=100.0) == 2000


def test_window_rotation_drops_old_samples():
    w = SlidingQuantiles(window_s=6, buckets=3, max_samples=100)
    w.observe(5.0, trace_id="old", now=0.5)     # bucket epoch 0
    w.observe(1.0, now=3.0)                     # bucket epoch 1
    qs = w.quantiles(now=4.0)
    assert qs[0.99] == 5.0                      # both in window
    # advance past the first bucket's window: only the 1.0 remains
    assert w.quantiles(now=7.9)[0.99] == 1.0
    assert w.worst(now=7.9) == (1.0, "")
    # advance past everything: empty window
    assert w.quantiles(now=60.0) is None
    assert w.worst(now=60.0) is None
    assert w.count(now=60.0) == 0


def test_window_bucket_reuse_after_wraparound():
    """A slot recycled after a full ring rotation must not resurrect
    its previous epoch's samples."""
    w = SlidingQuantiles(window_s=3, buckets=3, max_samples=10)
    w.observe(9.0, now=0.1)
    # same ring slot (epoch 0 and epoch 3 share index 0), later window
    w.observe(1.0, now=3.1)
    assert w.quantiles(now=3.5)[0.99] == 1.0


def test_window_reservoir_stays_bounded_and_worst_exact():
    w = SlidingQuantiles(window_s=60, buckets=1, max_samples=32)
    for i in range(1000):
        w.observe(float(i), trace_id=f"t{i}", now=1.0)
    assert len(w._ring[0].samples) == 32        # bounded memory
    assert w.count(now=1.0) == 1000             # true volume kept
    # the exemplar is exact even when its sample was reservoir-evicted
    assert w.worst(now=1.0) == (999.0, "t999")


def test_windows_registry_prometheus_and_snapshot():
    wins = QuantileWindows(window_s=60, buckets=6)
    wins.observe("x_seconds", 0.2, trace_id="deadbeef")
    wins.observe("x_seconds", 0.4, trace_id="cafe0001")
    text = wins.to_prometheus()
    assert '# TYPE x_seconds_window gauge' in text
    assert 'x_seconds_window{quantile="0.99"} 0.4' in text
    assert 'x_seconds_window_worst{trace_id="cafe0001"} 0.4' in text
    assert "x_seconds_window_count 2" in text
    snap = wins.snapshot()
    assert snap["x_seconds"]["count"] == 2
    assert snap["x_seconds"]["worst"]["trace_id"] == "cafe0001"
    assert snap["x_seconds"]["quantiles"]["p50"] == 0.2


# ---------------------------------------------- per-worker label folding

def test_prometheus_folds_worker_suffix_into_label():
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("serve_queue_depth").set(7)
    reg.gauge("serve_queue_depth_w0").set(3)
    reg.gauge("serve_queue_depth_w12").set(4)
    reg.counter("other_total").inc()
    text = reg.to_prometheus()
    assert 'serve_queue_depth{worker="0"} 3' in text
    assert 'serve_queue_depth{worker="12"} 4' in text
    assert "serve_queue_depth_w0" not in text   # folded, not flat
    # exactly one TYPE line for the folded family
    assert text.count("# TYPE serve_queue_depth gauge") == 1
    # JSON snapshots keep the flat names (backward compatibility)
    snap = reg.snapshot()
    assert snap["gauges"]["serve_queue_depth_w0"] == 3
    assert "serve_queue_depth{" not in json.dumps(snap)


def test_prometheus_fold_skips_mixed_kind_collisions():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("thing").inc(2)
    reg.gauge("thing_w1").set(5)    # would fold into a counter family
    text = reg.to_prometheus()
    assert "thing 2" in text
    assert "thing_w1 5" in text     # kept flat instead of mislabeled


# ----------------------------------------------------- atomic obs writes

def test_metrics_dump_and_trace_writes_are_atomic(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c_total").inc(3)
    path = str(tmp_path / "snap.json")
    reg.dump_json(path)
    assert json.load(open(path))["counters"]["c_total"] == 3
    sidecar = str(tmp_path / "q.trace")
    obs_trace.write_events(sidecar, [{"name": "a", "ts": 1}])
    assert obs_trace.read_events(sidecar) == [{"name": "a", "ts": 1}]
    merged = str(tmp_path / "trace.json")
    obs_trace.write_trace(merged, extra_events=[{"name": "b", "ts": 2}])
    assert {e["name"] for e in
            json.load(open(merged))["traceEvents"]} >= {"b"}
    # the atomic-write protocol leaves no temp debris behind
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ------------------------------------------------- trace ids in logging

def test_log_records_carry_trace_id_next_to_worker_id():
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
        log = get_logger("plane.test")
        set_worker_id(4)
        obs_trace.set_trace_id("feedc0de")
        log.info("traced record")
        obs_trace.set_trace_id(None)
        log.info("untraced record")
    finally:
        root.removeHandler(sink)
        set_verbosity(0)
        set_worker_id(None)
    assert "[w4 t:feedc0de]" in records[0]
    assert "[w4]" in records[1] and "t:" not in records[1]


# --------------------------------------- endpoints against a live frontend

def _ok_dispatcher(delay_s=0.0):
    def fn(wid, q, rconf, diff):
        if delay_s:
            time.sleep(delay_s)
        n = len(q)
        return (np.arange(n, dtype=np.int64), np.ones(n, np.int64),
                np.ones(n, bool))
    return CallableDispatcher(fn)


def test_endpoints_roundtrip_against_live_frontend():
    """/metrics serves live quantiles that move under load; /healthz
    follows the frontend's lifecycle; /statusz reports breaker + queue
    + replica state."""
    dc = DistributionController("mod", 2, 2, 64, replication=2)
    registry = resilience.BreakerRegistry(enabled=True)
    fe = ServingFrontend(
        dc, _ok_dispatcher(),
        sconf=ServeConfig(queue_depth=32, max_batch=8, max_wait_ms=1.0,
                          cache_bytes=0),
        registry=registry, breaker_key=lambda wid: ("h", wid))
    fe.start()
    srv = start_obs_server(
        0,
        health_fn=lambda: {"ok": fe._started and not fe._closed},
        status_providers={"serving": fe.statusz,
                          "device_programs": obs_device.snapshot})
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"
    try:
        n0 = obs_quantiles.WINDOWS.window(
            "serve_request_seconds").count()
        for i in range(24):
            assert fe.query(i % 64, (i + 1) % 64, timeout=30).ok
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert 'serve_request_seconds_window{quantile="0.5"}' in body
        assert 'serve_request_seconds_window{quantile="0.99"}' in body
        assert "serve_request_seconds_window_count" in body
        count1 = obs_quantiles.WINDOWS.window(
            "serve_request_seconds").count()
        assert count1 >= n0 + 24            # the window moved under load
        # cumulative registry rides the same scrape
        assert "serve_requests_total" in body
        h = urllib.request.urlopen(base + "/healthz")
        assert h.status == 200 and json.loads(h.read())["ok"]
        sz = json.loads(
            urllib.request.urlopen(base + "/statusz").read())
        serving = sz["serving"]
        assert serving["serving"] is True
        assert serving["replication"] == 2
        # per-shard queue depth + replica chain (the failover map)
        assert set(serving["shards"]) == {"0", "1"}
        assert serving["shards"]["0"]["replicas"] == [0, 1]
        assert "queue_depth" in serving["shards"]["0"]
        assert "breakers" in serving and "open" in serving["breakers"]
        assert "hedge" in serving and "rate" in serving["hedge"]
    finally:
        fe.stop()
        srv.close()
        registry.shutdown()
    # stopped frontend -> healthz goes 503 (probe semantics, no parsing)
    srv2 = start_obs_server(
        0, health_fn=lambda: {"ok": fe._started and not fe._closed})
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv2.port}/healthz")
        assert ei.value.code == 503
    finally:
        srv2.close()


def test_resolve_obs_port_flag_env_and_off(monkeypatch):
    monkeypatch.delenv("DOS_OBS_PORT", raising=False)
    assert resolve_obs_port(None) == (None, "off")   # default: off
    assert resolve_obs_port(-1) == (None, "off")     # negative: off
    assert resolve_obs_port(9100) == (9100, "flag")
    monkeypatch.setenv("DOS_OBS_PORT", "9200")
    assert resolve_obs_port(None) == (9200, "env")
    assert resolve_obs_port(9100) == (9100, "flag")  # flag wins
    monkeypatch.setenv("DOS_OBS_PORT", "junk")
    assert resolve_obs_port(None) == (None, "off")   # malformed:
    # degrade


def test_env_port_bind_failure_degrades_flag_port_raises(monkeypatch):
    """An unbindable DOS_OBS_PORT (e.g. inherited by every process of
    a fleet) disables endpoints with a warning; an explicit flag for
    the same port still raises — the operator named it."""
    holder = start_obs_server(0)
    try:
        taken = holder.port
        monkeypatch.setenv("DOS_OBS_PORT", str(taken))
        assert start_obs_server(None) is None      # env: degrade
        with pytest.raises(OSError):
            start_obs_server(taken)                # flag: raise
    finally:
        holder.close()


def test_supervisor_spawn_strips_obs_port_from_child_env(monkeypatch):
    """Supervised workers must not inherit the supervisor's
    DOS_OBS_PORT — N children contending for one socket is a
    crash-respawn loop."""
    import subprocess
    from distributed_oracle_search_tpu.utils.config import ClusterConfig
    from distributed_oracle_search_tpu.worker.supervisor import (
        SupervisedWorker, WorkerSupervisor,
    )

    monkeypatch.setenv("DOS_OBS_PORT", "9300")
    captured = {}

    def fake_popen(cmd, **kw):
        captured.update(kw)
        raise RuntimeError("stop before spawning anything")

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    conf = ClusterConfig(workers=["localhost"]).validate()
    sup = WorkerSupervisor(conf, conf_path="conf.json")
    with pytest.raises(RuntimeError):
        sup._spawn_server(SupervisedWorker(0, "/tmp/x.fifo"))
    assert "DOS_OBS_PORT" not in captured["env"]


def test_bench_numbers_survives_null_tail(tmp_path):
    p = str(tmp_path / "BENCH_r09.json")
    json.dump({"parsed": None, "tail": None}, open(p, "w"))
    assert obs_fleet.bench_numbers(p) == {}      # degrade, not crash


def test_exemplar_trace_id_propagates_from_traced_dispatch():
    """Every dispatched batch carries its shard-local number, tracing
    on or off; the windows' worst request and worst dispatch name it
    (``w<shard>.b<n>``) — the p99 -> profile link."""
    obs_quantiles.WINDOWS.reset()
    seen_batches = []

    def fn(wid, q, rconf, diff):
        # the runner dispatches inside the batch's tags: every span the
        # engine opens here would carry them
        tags = obs_trace.current_tags()
        assert tags["size"] == len(q)
        seen_batches.append(tags["batch"])
        n = len(q)
        return (np.zeros(n, np.int64), np.zeros(n, np.int64),
                np.ones(n, bool))

    dc = DistributionController("mod", 1, 1, 64)
    fe = ServingFrontend(
        dc, CallableDispatcher(fn),
        sconf=ServeConfig(queue_depth=16, max_batch=4, max_wait_ms=1.0,
                          cache_bytes=0))
    fe.start()
    try:
        for i in range(8):
            assert fe.query(i, i + 1, timeout=30).ok
    finally:
        fe.stop()
    # batches are numbered in order from 0 on the shard ...
    assert seen_batches == list(range(len(seen_batches)))
    ids = {f"w0.b{b}" for b in seen_batches}
    # ... and each window's exemplar is one of those SAME batches
    for name in ("serve_request_seconds", "serve_dispatch_seconds"):
        worst = obs_quantiles.WINDOWS.window(name).worst()
        assert worst is not None and worst[1] in ids


# ---------------------------------------------------------- fleet merge

def _snap(counters=None, gauges=None, hists=None):
    return {"counters": counters or {}, "gauges": gauges or {},
            "histograms": hists or {}}


def test_fleet_merge_sums_and_keeps_workers():
    h = {"count": 2, "sum": 0.5, "buckets": {"0.1": 1, "1.0": 2}}
    doc = obs_fleet.merge_snapshots([
        ("w0", _snap(counters={"a_total": 3}, gauges={"g": 1.0},
                     hists={"lat": h})),
        ("w1", _snap(counters={"a_total": 4, "b_total": 1},
                     gauges={"g": 2.0}, hists={"lat": h})),
    ])
    assert doc["n_workers"] == 2
    assert doc["fleet"]["counters"] == {"a_total": 7, "b_total": 1}
    assert doc["fleet"]["gauges"]["g"] == 3.0
    merged = doc["fleet"]["histograms"]["lat"]
    assert merged["count"] == 4 and merged["sum"] == 1.0
    assert merged["buckets"] == {"0.1": 2, "1.0": 4}
    assert set(doc["workers"]) == {"w0", "w1"}


def test_fleet_merge_disambiguates_conflicting_labels():
    doc = obs_fleet.merge_snapshots([
        ("w0", _snap(counters={"a": 1})),
        ("w0", _snap(counters={"a": 2})),
        ("w0", _snap(counters={"a": 4})),
    ])
    assert set(doc["workers"]) == {"w0", "w0#2", "w0#3"}
    # nothing was silently overwritten: the sum sees all three
    assert doc["fleet"]["counters"]["a"] == 7


def test_fleet_merge_histogram_bucket_mismatch_degrades():
    doc = obs_fleet.merge_snapshots([
        ("a", _snap(hists={"h": {"count": 1, "sum": 1.0,
                                 "buckets": {"1.0": 1}}})),
        ("b", _snap(hists={"h": {"count": 2, "sum": 2.0,
                                 "buckets": {"2.0": 2}}})),
    ])
    h = doc["fleet"]["histograms"]["h"]
    assert h["count"] == 3 and h["sum"] == 3.0
    assert h["buckets"] == {}      # count+sum kept, buckets dropped


def test_merge_traces_produces_one_perfetto_doc(tmp_path):
    head = str(tmp_path / "campaign.trace.json")
    json.dump({"traceEvents": [
        {"name": "head.send", "ts": 10, "ph": "X",
         "args": {"trace_id": "t1"}}]}, open(head, "w"))
    sidecar_dir = tmp_path / "nfs"
    sidecar_dir.mkdir()
    obs_trace.write_events(
        str(sidecar_dir / "q.host0.trace"),
        [{"name": "worker.search", "ts": 12, "ph": "X",
          "args": {"trace_id": "t1"}}])
    obs_trace.write_events(
        str(sidecar_dir / "q.host1.trace"),
        [{"name": "worker.search", "ts": 11, "ph": "X",
          "args": {"trace_id": "t2"}}])
    out = str(tmp_path / "merged.json")
    n = obs_fleet.merge_traces([head, str(sidecar_dir)], out)
    assert n == 3
    doc = json.load(open(out))
    assert "traceEvents" in doc and len(doc["traceEvents"]) == 3
    # sorted by ts so Perfetto streams it in timeline order
    assert [e["ts"] for e in doc["traceEvents"]] == [10, 11, 12]
    # head and worker spans of one batch still join on trace_id
    ids = {e["args"]["trace_id"] for e in doc["traceEvents"]}
    assert "t1" in ids and "t2" in ids


def test_dos_obs_cli_merge_commands(tmp_path, capsys):
    from distributed_oracle_search_tpu.cli.obs import main as obs_main

    s0 = str(tmp_path / "w0" / "obs_metrics.json")
    s1 = str(tmp_path / "w1" / "obs_metrics.json")
    for p, n in ((s0, 1), (s1, 2)):
        os.makedirs(os.path.dirname(p))
        json.dump(_snap(counters={"x_total": n}), open(p, "w"))
    out = str(tmp_path / "fleet_metrics.json")
    assert obs_main(["merge-metrics", "-o", out, s0, s1,
                     "--label", "w0", "--label", "w1"]) == 0
    doc = json.load(open(out))
    assert doc["fleet"]["counters"]["x_total"] == 3
    assert set(doc["workers"]) == {"w0", "w1"}


def test_top_renders_fleet_table_live_and_unreachable():
    # the REAL dos-serve shape: breakers nested under the "serving"
    # section (frontend.statusz), not a top-level provider
    srv = ObsServer(0, status_providers={
        "serving": lambda: {"serving": True, "shards": {
            "0": {"queue_depth": 3}, "1": {"queue_depth": 1}},
            "hedge": {"rate": 0.05},
            "breakers": {"open": 1, "breakers": {
                "('h', 0)": {"state": "open"},
                "('h', 1)": {"state": "closed"}}}},
    }).start()
    try:
        eps = {f"127.0.0.1:{srv.port}":
               obs_fleet.fetch_statusz(f"127.0.0.1:{srv.port}"),
               "127.0.0.1:1": obs_fleet.fetch_statusz("127.0.0.1:1",
                                                      timeout_s=0.2)}
        table = obs_fleet.render_top(eps)
    finally:
        srv.close()
    lines = table.splitlines()
    assert lines[0].startswith("endpoint")
    assert "queued" in lines[0] and "breakers_open" in lines[0]
    live = next(l for l in lines if f":{srv.port}" in l)
    assert " 4 " in live + " "      # 3 + 1 queued
    assert "UNREACHABLE" in table   # the dead endpoint is a row, not a
    # crash


# ------------------------------------------------------- device costs

def test_device_cost_capture_on_host_backend():
    import jax
    import jax.numpy as jnp

    obs_device.reset()

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((32, 32), jnp.float32)
    entry = obs_device.capture("test/matmul32", f, x)
    assert entry is not None and entry["flops"] > 0
    assert entry["bytes_accessed"] > 0
    snap = obs_device.snapshot()
    assert snap["test/matmul32"]["flops"] == entry["flops"]
    # second capture under the same key is a no-op cache hit
    assert obs_device.capture("test/matmul32", f, x) == entry
    text = obs_device.to_prometheus()
    assert 'device_program_flops{program="test/matmul32"}' in text
    gauge = obs_metrics.REGISTRY.snapshot()["gauges"]
    assert gauge["device_programs_analyzed"] == 1
    obs_device.reset()


def test_engine_captures_cost_per_program_key(tmp_path):
    """ShardEngine's first call at a new program key lands one entry in
    the device-cost store (FLOPs/bytes for the compiled walk program)."""
    from distributed_oracle_search_tpu.data import (
        Graph, ensure_synth_dataset, read_scen,
    )
    from distributed_oracle_search_tpu.worker.build import (
        main as build_main,
    )
    from distributed_oracle_search_tpu.worker.engine import ShardEngine
    from distributed_oracle_search_tpu.transport.wire import RuntimeConfig

    obs_device.reset()
    datadir = str(tmp_path / "data")
    paths = ensure_synth_dataset(datadir, width=6, height=5,
                                 n_queries=16, seed=9)
    outdir = os.path.join(datadir, "index")
    build_main(["--input", paths["xy"], "--partmethod", "mod",
                "--partkey", "1", "--workerid", "0", "--maxworker", "1",
                "--outdir", outdir])
    g = Graph.from_xy(paths["xy"])
    dc = DistributionController("mod", 1, 1, g.n)
    eng = ShardEngine(g, dc, 0, outdir)
    queries = read_scen(paths["scen"])[:8]
    eng.answer(queries, RuntimeConfig())
    snap = obs_device.snapshot()
    assert len(snap) == 1
    (key, entry), = snap.items()
    assert key.startswith("table-search/q")
    assert entry.get("flops", 0) >= 0
    assert entry["bytes_accessed"] > 0
    # steady-state repeat at the same key adds nothing
    eng.answer(queries, RuntimeConfig())
    assert len(obs_device.snapshot()) == 1
    # the chunked deadline path captures the CHUNK-wide program it
    # actually ran (even under --extract, where the jit bookkeeping
    # key stays batch-wide), never a never-executed full-batch shape
    eng.astar_chunk = 4
    eng.answer(queries, RuntimeConfig(time=10**12, extract=True,
                                      k_moves=4))
    assert any(k.startswith("table-search/q4/")
               for k in obs_device.snapshot()), obs_device.snapshot()
    obs_device.reset()


# ----------------------------------------------------------- bench gate

def _bench_record(path, headline, value=100.0):
    json.dump({"parsed": {"metric": "scenario_queries_per_sec",
                          "value": value, "unit": "queries/s",
                          "headline": headline}}, open(path, "w"))


def test_bench_diff_gates_regressions(tmp_path):
    from distributed_oracle_search_tpu.cli.obs import main as obs_main

    old = str(tmp_path / "BENCH_r01.json")
    new = str(tmp_path / "BENCH_r02.json")
    _bench_record(old, {"road_resident_queries_per_sec": 60000,
                        "serve_p99_ms": 10.0, "devices": 1})
    # clean round: small wobble inside tolerance + an improvement
    _bench_record(new, {"road_resident_queries_per_sec": 55000,
                        "serve_p99_ms": 8.0, "devices": 1})
    assert obs_main(["bench-diff", "--dir", str(tmp_path)]) == 0
    # regression round: throughput halves
    _bench_record(new, {"road_resident_queries_per_sec": 25000,
                        "serve_p99_ms": 10.0, "devices": 1})
    assert obs_main(["bench-diff", "--dir", str(tmp_path)]) == 1
    # latency-like keys gate in the OTHER direction
    _bench_record(new, {"road_resident_queries_per_sec": 60000,
                        "serve_p99_ms": 25.0, "devices": 1})
    assert obs_main(["bench-diff", "--dir", str(tmp_path)]) == 1
    # per-key tolerance overrides the default
    assert obs_main(["bench-diff", "--dir", str(tmp_path),
                     "--key-tolerance", "serve_p99_ms=2.0"]) == 0
    # value key (the headline scenario rate) is compared too
    _bench_record(new, {"devices": 1}, value=10.0)
    assert obs_main(["bench-diff", "--dir", str(tmp_path)]) == 1


def test_bench_diff_with_fewer_than_two_records(tmp_path):
    from distributed_oracle_search_tpu.cli.obs import main as obs_main

    assert obs_main(["bench-diff", "--dir", str(tmp_path)]) == 0


def test_bench_diff_reads_the_repo_records():
    """The real BENCH_r*.json trajectory parses and compares (the gate
    must work on the driver's record format, not just synthetic
    fixtures)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    records = obs_fleet.find_bench_records(here)
    parseable = [p for p in records if obs_fleet.bench_numbers(p)]
    if len(parseable) < 2:
        pytest.skip("repo carries fewer than two parseable records")
    out = obs_fleet.compare_bench(parseable[-2], parseable[-1],
                                  tolerance=1e9)  # parse check only
    assert out["checked"] > 0
