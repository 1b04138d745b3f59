"""Online serving layer: queues, micro-batching, cache, shed semantics.

The tier-1 smoke test is the acceptance gate: answers from the online
path must be identical to a batch campaign over the same queries on the
synth graph, overload must return ``BUSY`` (not a hang) when the queue
bound is hit, and on a skewed workload the cache-hit counter must move
and the micro-batcher must actually coalesce (mean dispatched batch
size > 1). The heavy open-loop Poisson latency drill stays behind
``slow``.
"""

import io
import os
import threading
import time

import numpy as np
import pytest

from distributed_oracle_search_tpu.data import ensure_synth_dataset, read_scen
from distributed_oracle_search_tpu.data.graph import Graph
from distributed_oracle_search_tpu.models.cpd import write_index_manifest
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.serving import (
    BUSY, CallableDispatcher, EngineDispatcher, FifoDispatcher, OK,
    ResultCache, ServeConfig, ServeRequest, ServingFrontend, ShardQueue,
    TIMEOUT, UNAVAILABLE, knob_fingerprint,
)
from distributed_oracle_search_tpu.serving import ingress
from distributed_oracle_search_tpu.transport import resilience
from distributed_oracle_search_tpu.transport.wire import RuntimeConfig
from distributed_oracle_search_tpu.utils.config import ClusterConfig
from distributed_oracle_search_tpu.worker import FifoServer, stop_server
from distributed_oracle_search_tpu.worker.build import main as build_main

pytestmark = pytest.mark.serve


# ------------------------------------------------------------- fixtures

@pytest.fixture(scope="module")
def serve_world(tmp_path_factory):
    """Small 2-shard world with a built CPD index (the test_drivers
    pattern): graph, controller, conf, and the scenario queries."""
    datadir = str(tmp_path_factory.mktemp("serve-data"))
    paths = ensure_synth_dataset(datadir, width=10, height=8,
                                 n_queries=96, seed=21)
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
    queries = read_scen(conf.scenfile)
    return conf, g, dc, queries


def _counter(name: str) -> float:
    return obs_metrics.REGISTRY.snapshot()["counters"].get(name, 0)


def _hist(name: str) -> dict:
    return obs_metrics.REGISTRY.snapshot()["histograms"][name]


def _mk_req(s, t, wid=0, deadline=None):
    return ServeRequest(s=s, t=t, wid=wid, key=(s, t, "-", ()),
                        t_submit=time.monotonic(), deadline=deadline)


# ----------------------------------------------------------- unit: knobs

def test_serve_config_env_and_overrides(monkeypatch):
    monkeypatch.setenv("DOS_SERVE_MAX_BATCH", "128")
    monkeypatch.setenv("DOS_SERVE_MAX_WAIT_MS", "2.5")
    monkeypatch.setenv("DOS_SERVE_QUEUE_DEPTH", "nonsense")  # degrades
    sc = ServeConfig.from_env(cache_bytes=0)
    assert sc.max_batch == 128
    assert sc.max_wait_ms == 2.5
    assert sc.queue_depth == ServeConfig.queue_depth
    assert sc.cache_bytes == 0


@pytest.mark.parametrize("bad", [
    dict(max_batch=0), dict(max_batch=48), dict(queue_depth=0),
    dict(deadline_ms=0), dict(cache_bytes=-1),
])
def test_serve_config_rejects_malformed(bad):
    with pytest.raises(ValueError):
        ServeConfig(**bad).validate()


# ----------------------------------------------------------- unit: cache

def test_result_cache_lru_eviction_and_counters():
    from distributed_oracle_search_tpu.serving.cache import ENTRY_BYTES

    cache = ResultCache(3 * ENTRY_BYTES)
    h0, m0, e0 = (_counter("serve_cache_hits_total"),
                  _counter("serve_cache_misses_total"),
                  _counter("serve_cache_evictions_total"))
    for i in range(4):
        cache.put((i, i, "-", ()), (i, 1, True))
    assert len(cache) == 3
    assert cache.get((0, 0, "-", ())) is None          # evicted (LRU)
    assert cache.get((3, 3, "-", ())) == (3, 1, True)
    # touching 1 makes 2 the LRU victim of the next insert
    assert cache.get((1, 1, "-", ())) is not None
    cache.put((9, 9, "-", ()), (9, 1, True))
    assert cache.get((2, 2, "-", ())) is None
    assert _counter("serve_cache_evictions_total") - e0 == 2
    assert _counter("serve_cache_hits_total") - h0 == 2
    assert _counter("serve_cache_misses_total") - m0 == 2


def test_result_cache_invalidate_by_diff_and_disabled():
    cache = ResultCache(1 << 20)
    cache.put((1, 2, "-", ()), (3, 1, True))
    cache.put((1, 2, "d1", ()), (5, 1, True))
    assert cache.invalidate("d1") == 1
    assert cache.get((1, 2, "-", ())) is not None
    assert cache.invalidate() == 1
    assert len(cache) == 0
    off = ResultCache(0)
    off.put((1, 2, "-", ()), (3, 1, True))
    assert off.get((1, 2, "-", ())) is None and not off.enabled


def test_knob_fingerprint_covers_answer_knobs():
    base = knob_fingerprint(RuntimeConfig())
    assert knob_fingerprint(RuntimeConfig(hscale=2.0)) != base
    assert knob_fingerprint(RuntimeConfig(k_moves=3)) != base
    assert knob_fingerprint(RuntimeConfig(time=10)) != base
    # presentation knobs stay out
    assert knob_fingerprint(RuntimeConfig(verbose=3, threads=7)) == base


# ----------------------------------------------------------- unit: queue

def test_shard_queue_bounded_and_never_blocks():
    q = ShardQueue(2)
    assert q.try_put(_mk_req(1, 2))
    assert q.try_put(_mk_req(3, 4))
    t0 = time.monotonic()
    assert not q.try_put(_mk_req(5, 6))        # full: immediate False
    assert time.monotonic() - t0 < 0.1
    q.close()
    assert not q.try_put(_mk_req(7, 8))        # closed: immediate False
    assert len(q.drain()) == 2


def test_shard_queue_batch_flush_on_size_and_wait():
    q = ShardQueue(64)
    stop = threading.Event()
    for i in range(5):
        q.try_put(_mk_req(i, i))
    # size flush: 4 of 5 immediately, no max_wait sleep
    t0 = time.monotonic()
    batch = q.get_batch(4, max_wait_s=5.0, stop=stop)
    assert len(batch) == 4 and time.monotonic() - t0 < 1.0
    # wait flush: the leftover flushes alone once max_wait expires
    batch = q.get_batch(4, max_wait_s=0.05, stop=stop)
    assert len(batch) == 1
    stop.set()
    assert q.get_batch(4, 0.01, stop) == []


# ------------------------------------------------- frontend: smoke gate

def test_online_answers_match_campaign_path(serve_world):
    """Acceptance smoke: frontend + in-process shard engines round-trip
    ~100 queries (some duplicated); answers are identical to the
    campaign path (``ShardEngine.answer`` over the grouped batch), the
    skewed repeats hit the cache, and the micro-batcher coalesces."""
    conf, g, dc, queries = serve_world
    base = queries[:64]
    rng = np.random.default_rng(5)
    # zipf-ish skew: repeats drawn heavily from the head of the pool
    reps = base[rng.zipf(1.5, size=40).clip(1, len(base)) - 1]
    workload = np.concatenate([base, reps])
    assert len(workload) >= 100

    rconf = RuntimeConfig()
    dispatcher = EngineDispatcher(conf, graph=g, dc=dc)
    sconf = ServeConfig(max_batch=32, max_wait_ms=50.0, queue_depth=256)
    fe = ServingFrontend(dc, dispatcher, sconf=sconf, rconf=rconf)
    fe.start()
    hits0 = _counter("serve_cache_hits_total")
    fill0 = _hist("serve_batch_fill")
    try:
        # phase 1: the unique pool, submitted back-to-back so batches
        # can form; phase 2: the skewed repeats (now cache-resident)
        futs = [fe.submit(s, t) for s, t in base]
        res = [f.result(30) for f in futs]
        futs2 = [fe.submit(s, t) for s, t in reps]
        res2 = [f.result(30) for f in futs2]
    finally:
        fe.stop()
    assert all(r.ok for r in res + res2)

    # golden: the campaign path over the same queries, grouped by owner
    cost = np.zeros(len(workload), np.int64)
    plen = np.zeros(len(workload), np.int64)
    fin = np.zeros(len(workload), bool)
    for wid, part in dc.group_queries(workload).items():
        mask = dc.worker_of(workload[:, 1]) == wid
        c, p, f, _ = dispatcher._engine_for(wid).answer(part, rconf)
        cost[mask], plen[mask], fin[mask] = c, p, f
    got = res + res2
    assert [r.cost for r in got] == cost.tolist()
    assert [r.plen for r in got] == plen.tolist()
    assert [r.finished for r in got] == fin.tolist()

    assert _counter("serve_cache_hits_total") - hits0 > 0
    assert any(r.cached for r in res2)
    fill1 = _hist("serve_batch_fill")
    n_batches = fill1["count"] - fill0["count"]
    assert n_batches > 0
    mean_fill = (fill1["sum"] - fill0["sum"]) / n_batches
    assert mean_fill > 1.0, f"micro-batcher never coalesced: {mean_fill}"


def test_overload_sheds_busy_immediately():
    """A full shard queue answers BUSY at once — the shed path must
    never hang the submitter behind a stuck shard."""
    dc = DistributionController("mod", 1, 1, 64)
    release = threading.Event()

    def slow(wid, q, rconf, diff):
        release.wait(10)
        n = len(q)
        return (np.zeros(n, np.int64), np.zeros(n, np.int64),
                np.ones(n, bool))

    sconf = ServeConfig(queue_depth=4, max_batch=2, max_wait_ms=1.0,
                        cache_bytes=0)
    fe = ServingFrontend(dc, CallableDispatcher(slow), sconf=sconf)
    fe.start()
    busy0 = _counter("serve_shed_busy_total")
    try:
        futs = [fe.submit(i, i + 1) for i in range(12)]
        t0 = time.monotonic()
        shed = [f for f in futs if f.done()
                and f.result(0).status == BUSY]
        # depth 4 + at most one forming/in-flight batch: most of the 12
        # must have shed, and instantly (no queue wait, no dispatch)
        assert len(shed) >= 4
        assert time.monotonic() - t0 < 1.0
        assert _counter("serve_shed_busy_total") - busy0 == len(shed)
    finally:
        release.set()
        fe.stop()
    # the admitted ones still terminate (drained on release)
    assert all(f.done() for f in futs)


def test_open_breaker_sheds_unavailable():
    dc = DistributionController("mod", 1, 1, 64)

    def never(wid, q, rconf, diff):  # pragma: no cover - breaker sheds
        raise AssertionError("dispatch must not run")

    registry = resilience.BreakerRegistry(threshold=1, cooldown_s=60.0,
                                          enabled=True)
    registry.record(0, ok=False)               # force breaker OPEN
    fe = ServingFrontend(dc, CallableDispatcher(never),
                         sconf=ServeConfig(cache_bytes=0),
                         registry=registry)
    fe.start()
    try:
        res = fe.query(1, 2, timeout=5)
        assert res.status == UNAVAILABLE and res.detail == "circuit-open"
    finally:
        fe.stop()
        registry.shutdown()


def test_dispatch_failure_records_breaker_and_errors():
    dc = DistributionController("mod", 1, 1, 64)

    def broken(wid, q, rconf, diff):
        raise RuntimeError("shard down")

    registry = resilience.BreakerRegistry(threshold=2, cooldown_s=60.0,
                                          enabled=True)
    fe = ServingFrontend(dc, CallableDispatcher(broken),
                         sconf=ServeConfig(max_wait_ms=1.0,
                                           cache_bytes=0),
                         registry=registry)
    fe.start()
    try:
        r1 = fe.query(1, 2, timeout=10)
        assert r1.status == "ERROR" and "shard down" in r1.detail
        r2 = fe.query(3, 4, timeout=10)
        assert r2.status == "ERROR"
        # two failed batches tripped the breaker: now shed, not dispatch
        r3 = fe.query(5, 6, timeout=10)
        assert r3.status == UNAVAILABLE
    finally:
        fe.stop()
        registry.shutdown()


def test_deadline_expires_queued_requests():
    dc = DistributionController("mod", 1, 1, 64)
    release = threading.Event()
    dispatched = []

    def gated(wid, q, rconf, diff):
        dispatched.append(np.array(q))
        release.wait(10)
        n = len(q)
        return (np.zeros(n, np.int64), np.zeros(n, np.int64),
                np.ones(n, bool))

    sconf = ServeConfig(max_batch=2, max_wait_ms=1.0, deadline_ms=200.0,
                        cache_bytes=0)
    fe = ServingFrontend(dc, CallableDispatcher(gated), sconf=sconf)
    fe.start()
    try:
        f1 = fe.submit(1, 2)                 # heads straight into flight
        for _ in range(100):
            if dispatched:
                break
            time.sleep(0.01)
        f2 = fe.submit(3, 4)                 # queues behind the gate
        time.sleep(0.4)                      # > deadline_ms
        release.set()
        assert f1.result(10).ok
        assert f2.result(10).status == TIMEOUT
    finally:
        release.set()
        fe.stop()


def test_diff_change_invalidates_cache(serve_world):
    conf, g, dc, queries = serve_world
    fe = ServingFrontend(dc, EngineDispatcher(conf, graph=g, dc=dc),
                         sconf=ServeConfig(max_wait_ms=1.0), diff="-")
    fe.start()
    try:
        s, t = map(int, queries[0])
        free = fe.query(s, t, timeout=30)
        assert free.ok
        assert fe.query(s, t, timeout=30).cached
        fe.set_diff(conf.diffs[1])
        perturbed = fe.query(s, t, timeout=30)
        assert perturbed.ok and not perturbed.cached
        # costs accumulate on perturbed weights (>= free flow)
        assert perturbed.cost >= free.cost
    finally:
        fe.stop()


def test_warm_compiles_every_batch_size_before_traffic(serve_world):
    """Warming loads the indexed shards and compiles their walk at
    every power of two up to max_batch, so live batches of any size
    compile nothing."""
    conf, g, dc, queries = serve_world
    dispatcher = EngineDispatcher(conf, graph=g, dc=dc)
    assert dispatcher.indexed_shards() == [0, 1]
    fe = ServingFrontend(dc, dispatcher,
                         sconf=ServeConfig(max_batch=16, max_wait_ms=1.0),
                         diff=conf.diffs[1])
    assert fe.warm(dispatcher.indexed_shards()) == 2 * 5   # 1..16
    for wid in (0, 1):
        assert len(dispatcher._engine_for(wid)._jit_seen) == 5
    compiles0 = _hist("worker_jit_compile_seconds")["count"]
    fe.start()
    try:
        futs = [fe.submit(int(s), int(t)) for s, t in queries[:40]]
        res = [f.result(30) for f in futs]
    finally:
        fe.stop()
    assert all(r.ok for r in res)
    assert _hist("worker_jit_compile_seconds")["count"] == compiles0


# ------------------------------------------------------ wire: fifo path

def test_fifo_dispatcher_roundtrips_results(serve_world, tmp_path):
    """The host-backend dispatch: a resident FifoServer answers the
    stats line AND the per-query `.results` sidecar; answers match the
    in-process engines."""
    conf, g, dc, queries = serve_world
    fifo = str(tmp_path / "serve-worker1.fifo")
    server = FifoServer(conf, 1, command_fifo=fifo)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    for _ in range(100):
        if os.path.exists(fifo):
            break
        time.sleep(0.02)
    else:
        pytest.fail("server fifo never appeared")
    try:
        import distributed_oracle_search_tpu.serving.dispatch as disp

        mine = queries[dc.worker_of(queries[:, 1]) == 1][:8]
        fd = FifoDispatcher(conf, timeout=60.0)
        orig = disp.command_fifo_path
        disp.command_fifo_path = lambda wid: fifo
        try:
            cost, plen, fin = fd.answer_batch(1, mine, RuntimeConfig(),
                                              "-")
        finally:
            disp.command_fifo_path = orig
        c2, p2, f2, _ = server.engine.answer(mine, RuntimeConfig())
        assert (cost == c2).all() and (plen == p2).all()
        assert (fin == f2).all()
    finally:
        stop_server(fifo)
        th.join(timeout=10)


# --------------------------------------------------------- line protocol

def test_line_protocol_stream(serve_world):
    conf, g, dc, queries = serve_world
    fe = ServingFrontend(dc, EngineDispatcher(conf, graph=g, dc=dc),
                         sconf=ServeConfig(max_wait_ms=5.0))
    fe.start()
    try:
        s0, t0 = map(int, queries[0])
        s1, t1 = map(int, queries[1])
        rfile = io.StringIO(
            f"{s0} {t0}\n"
            "# a comment\n"
            "\n"
            f"{s1} {t1}\n"
            "not a query\n"
            f"{s0} {t0}\n"
            "quit\n"
            f"{s1} {t1}\n")          # after quit: ignored
        wfile = io.StringIO()
        n = ingress.serve_stream(fe, rfile, wfile)
    finally:
        fe.stop()
    assert n == 3
    lines = wfile.getvalue().strip().splitlines()
    assert len(lines) == 4                    # 3 queries + 1 malformed
    assert lines[0].startswith(f"OK {s0} {t0} ")
    assert lines[1].startswith(f"OK {s1} {t1} ")
    assert lines[2].startswith("ERROR -1 -1 malformed-line")
    # the repeat answers identically whether it was batched with the
    # first ask (engine dedup) or served from the cache
    assert lines[3].split()[:6] == lines[0].split()[:6]


# ---------------------------------------------------- slow: poisson drill

@pytest.mark.slow
def test_poisson_open_loop_latency_drill(serve_world):
    """Open-loop Poisson load against the in-process shards: every
    request terminates, tail latency is measurable, the batcher
    coalesces under pressure, and sheds (if any) are explicit."""
    conf, g, dc, queries = serve_world
    dispatcher = EngineDispatcher(conf, graph=g, dc=dc)
    rconf = RuntimeConfig()
    # warm every power-of-two program the load can hit, off the clock
    # (XLA compiles mid-drill would back the queue up past any deadline)
    for wid in range(dc.maxworker):
        own = dc.owned(wid)
        for b in (1, 2, 4, 8, 16, 32, 64):
            t = np.resize(own, b)
            s = (t + np.arange(b) + 1) % g.n     # distinct (s, t) pairs
            dispatcher.answer_batch(
                wid, np.stack([s, t], axis=1), rconf, "-")
    fe = ServingFrontend(dc, dispatcher,
                         sconf=ServeConfig(max_batch=64, max_wait_ms=2.0,
                                           queue_depth=512,
                                           deadline_ms=60_000.0))
    fe.start()
    try:
        rng = np.random.default_rng(11)
        n = 2000
        pool = queries[rng.zipf(1.4, size=n).clip(1, len(queries)) - 1]
        gaps = rng.exponential(1.0 / 4000.0, size=n)   # ~4k rps offered
        t0 = time.monotonic()
        arrivals = t0 + np.cumsum(gaps)
        futs = []
        for (s, t), at in zip(pool, arrivals):
            now = time.monotonic()
            if at > now:
                time.sleep(at - now)
            futs.append(fe.submit(int(s), int(t)))
        res = [f.result(60) for f in futs]
        lat = np.array([r.t_done for r in res]) - arrivals
        assert all(r.status in (OK, BUSY) for r in res)
        n_ok = sum(r.ok for r in res)
        assert n_ok > 0.5 * n
        p99 = float(np.percentile(lat[[r.ok for r in res]], 99))
        assert 0 < p99 < 60.0
        fill = _hist("serve_batch_fill")
        assert fill["sum"] / max(fill["count"], 1) > 1.0
    finally:
        fe.stop()
