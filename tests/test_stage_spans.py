"""Stage spans and wait counters on the served path.

Each histogram is observed once per request, batch or frame, over the
interval its name promises: checked with a fake engine that sleeps a
known time (the micro-batcher's waits, the gateway's frame and reply),
and with a real shard engine on a small city (the fetch and the device
gap). Every stage span reaches both sinks with its batch number: the
Chrome buffer, and a CPU ``jax.profiler`` trace's ``/host:CPU`` plane.
``obs.trace.span`` imports no JAX into a process that has none.
"""

import glob
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distributed_oracle_search_tpu.data import ensure_synth_dataset, read_scen
from distributed_oracle_search_tpu.data.graph import Graph
from distributed_oracle_search_tpu.gateway import (
    GatewayConfig, GatewayServer,
)
from distributed_oracle_search_tpu.gateway import protocol
from distributed_oracle_search_tpu.gateway import server as gw_server
from distributed_oracle_search_tpu.models.cpd import write_index_manifest
from distributed_oracle_search_tpu.obs import trace as obs_trace
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.serving import (
    CallableDispatcher, ServeConfig, ServingFrontend,
)
from distributed_oracle_search_tpu.serving import batcher as sv_batcher
from distributed_oracle_search_tpu.transport.frames import (
    FrameReader, FrameWriter,
)
from distributed_oracle_search_tpu.transport.wire import RuntimeConfig
from distributed_oracle_search_tpu.utils.config import ClusterConfig
from distributed_oracle_search_tpu.worker import engine as wk_engine
from distributed_oracle_search_tpu.worker.build import main as build_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the runner thread's leaf spans, per batch
RUNNER = ("serve.wait", "serve.dispatch", "serve.finish")


@pytest.fixture(autouse=True)
def _clean_trace_state():
    yield
    obs_trace.enable(False)
    obs_trace.clear()
    obs_trace.set_trace_id(None)


def _sleeper(seconds: float):
    """A fake engine: answers ``|s - t|`` after ``seconds``."""
    def answer(wid, q, rconf, diff):
        time.sleep(seconds)
        q = np.asarray(q)
        return (np.abs(q[:, 0] - q[:, 1]).astype(np.int64),
                np.ones(len(q), np.int64), np.ones(len(q), bool))
    return answer


def _frontend(dispatch, **kw):
    sconf = ServeConfig(**{"queue_depth": 64, "max_wait_ms": 1.0,
                           "cache_bytes": 0, **kw}).validate()
    return ServingFrontend(DistributionController("mod", 1, 1, 64),
                           CallableDispatcher(dispatch),
                           sconf=sconf).start()


def _delta(hist, before):
    return hist.count - before[0], hist.sum - before[1]


def _snap(hist):
    return hist.count, hist.sum


# ------------------------------------------------------ micro-batcher

def test_queue_and_handoff_waits_per_request_and_per_batch():
    """Four single-request batches at once in front of an engine that
    takes T each: the runner pops them at 0, T, 2T, 3T, so the queue
    waits sum to 0 + T + 2T + 3T = 6T, and each batch's dispatch starts
    as soon as it is popped, so the handoff waits are next to nothing."""
    T = 0.2
    q0 = _snap(sv_batcher.H_QUEUE_WAIT)
    h0 = _snap(sv_batcher.H_HANDOFF_WAIT)
    d0 = _snap(sv_batcher.H_DISPATCH)
    fe = _frontend(_sleeper(T), max_batch=1)
    try:
        futs = [fe.submit(i, i + 1) for i in range(4)]
        res = [f.result(30) for f in futs]
    finally:
        fe.stop()
    assert all(r.ok for r in res)
    assert sorted(r.batch for r in res) == [0, 1, 2, 3]
    n, qsum = _delta(sv_batcher.H_QUEUE_WAIT, q0)
    assert n == 4                               # once per request
    assert 6 * T * 0.9 <= qsum <= 6 * T + 0.3
    n, hsum = _delta(sv_batcher.H_HANDOFF_WAIT, h0)
    assert n == 4                               # once per batch
    assert 0 <= hsum < 0.05
    n, dsum = _delta(sv_batcher.H_DISPATCH, d0)
    assert n == 4 and 4 * T <= dsum <= 4 * T + 0.3


def test_batch_numbers_follow_the_batch_through_every_span():
    """With Chrome collection on, the runner's spans of each batch
    carry the same ``batch=`` number, and those after the batch formed
    carry its size."""
    obs_trace.enable()
    fe = _frontend(_sleeper(0.01), max_batch=4)
    try:
        futs = [fe.submit(i, i + 1) for i in range(4)]
        res = [f.result(30) for f in futs]
        later = fe.submit(9, 10).result(30)
    finally:
        fe.stop()
    by_batch = {}
    for r in res + [later]:
        by_batch.setdefault(r.batch, 0)
        by_batch[r.batch] += 1
    evs = obs_trace.events()
    for b, size in by_batch.items():
        names = {e["name"] for e in evs if e["args"].get("batch") == b}
        assert set(RUNNER) <= names, (b, names)
        for e in evs:
            if e["args"].get("batch") == b and e["name"] in (
                    "serve.dispatch", "serve.finish"):
                assert e["args"]["size"] == size


def _gated(entered: threading.Event, gate: threading.Event):
    """A fake engine that flags its first call and holds every call
    until ``gate`` opens."""
    def answer(wid, q, rconf, diff):
        entered.set()
        assert gate.wait(30)
        return _sleeper(0)(wid, q, rconf, diff)
    return answer


def test_nothing_forms_ahead_of_the_runner():
    """While one batch is in dispatch, every later request is still in
    the shard's queue (the queue bound covers them all): no batch forms
    until the runner is free to run it. Released, they answer in order,
    one batch each."""
    entered, gate = threading.Event(), threading.Event()
    fe = _frontend(_gated(entered, gate), max_batch=1, queue_depth=8)
    try:
        futs = [fe.submit(i, i + 1) for i in range(5)]
        assert entered.wait(30)
        queue = fe._queues[0]
        assert len(queue) == 4
        time.sleep(0.1)
        assert len(queue) == 4
        gate.set()
        res = [f.result(30) for f in futs]
    finally:
        gate.set()
        fe.stop()
    assert all(r.ok for r in res)
    assert [r.cost for r in res] == [1] * 5
    assert [r.batch for r in res] == [0, 1, 2, 3, 4]


def test_stop_fails_the_queued_requests_and_joins_the_runner():
    """``stop`` with a batch in dispatch and four requests queued past
    its drain budget: the batch in flight answers once stop gave up
    draining, the queued ones complete ``shutdown`` errors, and the
    shard's thread is gone."""
    entered, gate = threading.Event(), threading.Event()
    before = {t.name for t in threading.enumerate()}
    fe = _frontend(_gated(entered, gate), max_batch=1, queue_depth=8)
    futs = [fe.submit(i, i + 1) for i in range(5)]
    assert entered.wait(30)
    stopping = fe._batchers[0]._stop

    def open_when_stopping():
        stopping.wait(30)
        gate.set()

    opener = threading.Thread(target=open_when_stopping)
    opener.start()
    try:
        fe.stop(drain_s=0.1)
    finally:
        gate.set()
        opener.join()
    assert all(f.done() for f in futs)
    res = [f.result(0) for f in futs]
    assert res[0].ok
    assert [(r.status, r.detail) for r in res[1:]] == [
        ("ERROR", "shutdown")] * 4
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("dos-serve-") and t.is_alive()
                and t.name not in before]


# ------------------------------------------------------------ engine

@pytest.fixture(scope="module")
def city(tmp_path_factory):
    datadir = str(tmp_path_factory.mktemp("spans-data"))
    paths = ensure_synth_dataset(datadir, width=10, height=8,
                                 n_queries=64, seed=23)
    conf = ClusterConfig(
        workers=["localhost"], partmethod="mod", partkey=1,
        outdir=os.path.join(datadir, "index"), xy_file=paths["xy"],
        scenfile=paths["scen"], diffs=["-", paths["diff"]], nfs=datadir,
    ).validate()
    build_main(["--input", conf.xy_file, "--partmethod", "mod",
                "--partkey", "1", "--workerid", "0", "--maxworker", "1",
                "--outdir", conf.outdir])
    g = Graph.from_xy(conf.xy_file)
    dc = DistributionController("mod", 1, 1, g.n)
    write_index_manifest(conf.outdir, dc)
    return conf, g, dc, read_scen(conf.scenfile)


def test_engine_fetch_and_device_gap_boundaries(city):
    """``worker_fetch_seconds`` once per batch, after the walk and
    inside the call; ``worker_device_gap_seconds`` once per batch after
    the engine's first, from the previous answers to this walk."""
    conf, g, dc, queries = city
    eng = wk_engine.ShardEngine(g, dc, 0, conf.outdir)
    rc = RuntimeConfig()
    eng.answer(queries[:4], rc)                 # compiles
    f0 = _snap(wk_engine.M_FETCH)
    g0 = _snap(wk_engine.M_DEVICE_GAP)
    r0 = _snap(wk_engine.M_RECEIVE)
    s0 = _snap(wk_engine.M_SEARCH)
    t_ret = time.perf_counter()
    time.sleep(0.2)
    t_a = time.perf_counter()
    eng.answer(queries[:4], rc)
    t_b = time.perf_counter()
    nf, fetch = _delta(wk_engine.M_FETCH, f0)
    ng, gap = _delta(wk_engine.M_DEVICE_GAP, g0)
    prep = _delta(wk_engine.M_RECEIVE, r0)[1]
    walk = _delta(wk_engine.M_SEARCH, s0)[1]
    assert nf == 1 and ng == 1
    assert 0 < fetch and prep + walk + fetch <= t_b - t_a
    # the gap holds the sleep and this batch's prep, not its walk
    assert 0.2 + prep <= gap + 1e-3
    assert gap <= t_b - t_ret - walk - fetch + 1e-3
    # a fresh engine books no gap for its first batch
    eng2 = wk_engine.ShardEngine(g, dc, 0, conf.outdir)
    eng2.answer(queries[:4], rc)
    assert _delta(wk_engine.M_DEVICE_GAP, g0)[0] == 1
    assert _delta(wk_engine.M_FETCH, f0)[0] == 2


def test_engine_spans_nest_in_order_under_the_batch_tags(city):
    conf, g, dc, queries = city
    eng = wk_engine.ShardEngine(g, dc, 0, conf.outdir)
    eng.answer(queries[:4], RuntimeConfig())
    obs_trace.enable()
    with obs_trace.tagged(batch=9, size=4):
        eng.answer(queries[:4], RuntimeConfig())
    evs = {e["name"]: e for e in obs_trace.events()}
    stages = ["worker.prep", "worker.walk", "worker.fetch"]
    assert set(stages + ["worker.weights"]) <= set(evs)
    for name in stages + ["worker.weights"]:
        assert evs[name]["args"]["batch"] == 9
        assert evs[name]["args"]["size"] == 4
    prep, w = evs["worker.prep"], evs["worker.weights"]
    assert prep["ts"] <= w["ts"]
    assert w["ts"] + w["dur"] <= prep["ts"] + prep["dur"] + 1
    for a, b in zip(stages, stages[1:]):
        assert evs[a]["ts"] + evs[a]["dur"] <= evs[b]["ts"] + 1


# ------------------------------------------------------------ gateway

def _gconf(tmp_path):
    return GatewayConfig(replicas=1, socket_dir=str(tmp_path), credit=32,
                         deadline_ms=60_000.0).validate()


def test_gateway_frame_and_reply_per_frame(tmp_path):
    """Over a socket: each admitted query frame books one
    ``gateway_frame_seconds``, read off the socket until the reply was
    written (so at least the engine's sleep), and one
    ``gateway_reply_seconds``, from its last answer to the reply
    written (a small share of it). A busy frame books neither."""
    T = 0.15
    obs_trace.enable()
    fe = _frontend(_sleeper(T), max_batch=4)
    srv = GatewayServer(fe, fid=0, gconf=_gconf(tmp_path)).start()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    f0 = _snap(gw_server.H_FRAME)
    r0 = _snap(gw_server.H_REPLY)
    try:
        sock.connect(srv.socket_path)
        reader, writer = FrameReader(sock), FrameWriter(sock)
        assert reader.read().kind == "hello"
        t0 = time.monotonic()
        for fid in range(2):
            h, a = protocol.encode_pairs(fid, [(1, 2), (3, 9)])
            writer.send(h, a)
        replies = [reader.read() for _ in range(2)]
        took = time.monotonic() - t0
    finally:
        sock.close()
        srv.stop()
        fe.stop()
    assert [r.kind for r in replies] == ["r", "r"]
    n, frame = _delta(gw_server.H_FRAME, f0)
    assert n == 2 and 2 * T <= frame <= 2 * took
    n, reply = _delta(gw_server.H_REPLY, r0)
    assert n == 2 and 0 <= reply < frame / 4
    evs = obs_trace.events()
    assert sum(e["name"] == "gateway.frame" for e in evs) == 2
    replies_ev = [e for e in evs if e["name"] == "gateway.reply"]
    assert len(replies_ev) == 2
    # the reply names the batches that answered it, which the runner's
    # spans carry too
    batches = {int(b) for e in replies_ev
               for b in e["args"]["batches"].split(",")}
    finishes = {e["args"]["batch"] for e in evs
                if e["name"] == "serve.finish"}
    assert batches and batches <= finishes


# ----------------------------------------------------------- profiler

def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, dict(ev.stats)))
    return out


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    """Under a CPU ``jax.profiler`` trace, ``span`` is a
    ``TraceAnnotation``: the host plane shows it with its ``batch=``,
    from the thread's tags, and the served path's stage spans with
    theirs, with no Chrome collection on."""
    import jax

    assert not obs_trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.tagged(batch=41, size=3):
            with obs_trace.span("worker.walk"):
                time.sleep(0.001)
        fe = _frontend(_sleeper(0.005), max_batch=4)
        try:
            res = fe.submit(2, 5).result(30)
        finally:
            fe.stop()
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    walk = [st for name, st in evs if name == "worker.walk"]
    assert walk and walk[0]["batch"] == 41 and walk[0]["size"] == 3
    for name in RUNNER:
        got = [st for n, st in evs if n == name
               and st.get("batch") == res.batch]
        assert got, name
    assert not obs_trace.events()              # the Chrome sink stayed off


def test_span_imports_no_jax_into_a_jax_free_process():
    """The served path's spans, Chrome collection on and off, in a
    process that never imported JAX: it still has none after."""
    code = (
        "import sys\n"
        "from distributed_oracle_search_tpu.obs import trace\n"
        "from distributed_oracle_search_tpu.gateway import server\n"
        "from distributed_oracle_search_tpu.parallel.partition import "
        "DistributionController\n"
        "from distributed_oracle_search_tpu.serving import "
        "CallableDispatcher, ServeConfig, ServingFrontend\n"
        "import numpy as np\n"
        "def fn(wid, q, rconf, diff):\n"
        "    n = len(q)\n"
        "    return (np.zeros(n, np.int64), np.zeros(n, np.int64),\n"
        "            np.ones(n, bool))\n"
        "for on in (False, True):\n"
        "    trace.enable(on)\n"
        "    with trace.tagged(batch=1, size=1):\n"
        "        with trace.span('worker.walk', k=1):\n"
        "            pass\n"
        "    fe = ServingFrontend(DistributionController('mod', 1, 1, 8),\n"
        "                         CallableDispatcher(fn),\n"
        "                         sconf=ServeConfig(cache_bytes=0)).start()\n"
        "    assert fe.query(1, 2, timeout=30).ok\n"
        "    fe.stop()\n"
        "assert any(e['name'] == 'serve.finish' for e in trace.events())\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DOS_LOCK")}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
