"""Gateway tier: binary protocol codecs, the accept loop + DosClient
end to end across all four families, credit-window backpressure,
malformed-frame hygiene, the worker-side L2 cache across diff-epoch
swaps and membership commits, the kill-one-frontend drill, and the
control/obs satellites (credit occupancy signal, fleet columns, bench
key pins).
"""

import os
import socket
import threading
import time
import types

import numpy as np
import pytest

from distributed_oracle_search_tpu.data import ensure_synth_dataset, read_scen
from distributed_oracle_search_tpu.data.formats import write_diff
from distributed_oracle_search_tpu.data.graph import Graph
from distributed_oracle_search_tpu.gateway import (
    DosClient, GatewayBusy, GatewayConfig, GatewayError, GatewayServer,
    GatewayTier, GATEWAY_SCHEMA_VERSION, GatewayProtocolError,
    GatewaySchemaError,
)
from distributed_oracle_search_tpu.gateway import protocol
from distributed_oracle_search_tpu.models.cpd import write_index_manifest
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.parallel import membership
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.serving import (
    CallableDispatcher, EngineDispatcher, ServeConfig, ServingFrontend,
)
from distributed_oracle_search_tpu.traffic import QueryFamilies
from distributed_oracle_search_tpu.transport.frames import (
    Frame, FrameReader, FrameWriter, TransportError,
)
from distributed_oracle_search_tpu.transport.wire import RuntimeConfig
from distributed_oracle_search_tpu.utils.config import ClusterConfig
from distributed_oracle_search_tpu.worker.build import main as build_main
from distributed_oracle_search_tpu.worker.server import FifoServer

pytestmark = pytest.mark.gateway


def _counter(name: str) -> float:
    return obs_metrics.REGISTRY.snapshot()["counters"].get(name, 0)


# ------------------------------------------------------------- fixtures

@pytest.fixture(scope="module")
def gw_world(tmp_path_factory):
    """One-worker world with a built CPD index (the traffic_world
    pattern, single shard keeps it quick)."""
    datadir = str(tmp_path_factory.mktemp("gw-data"))
    paths = ensure_synth_dataset(datadir, width=10, height=8,
                                 n_queries=64, seed=51)
    conf = ClusterConfig(
        workers=["localhost"], partmethod="mod", partkey=1,
        outdir=os.path.join(datadir, "index"),
        xy_file=paths["xy"], scenfile=paths["scen"],
        diffs=["-", paths["diff"]], nfs=datadir,
    ).validate()
    build_main(["--input", conf.xy_file, "--partmethod",
                conf.partmethod, "--partkey", str(conf.partkey),
                "--workerid", "0", "--maxworker", "1",
                "--outdir", conf.outdir])
    g = Graph.from_xy(conf.xy_file)
    dc = DistributionController("mod", 1, 1, g.n)
    write_index_manifest(conf.outdir, dc)
    queries = read_scen(conf.scenfile)
    dispatcher = EngineDispatcher(conf, graph=g, dc=dc)
    return conf, g, dc, queries, dispatcher


def _frontend(dc, dispatcher, **kw):
    sconf = ServeConfig(**{"queue_depth": 1024, "max_wait_ms": 1.0,
                           "cache_bytes": 0, **kw}).validate()
    fe = ServingFrontend(dc, dispatcher, sconf=sconf)
    fe.start()
    return fe


def _gconf(tmp_path, **kw):
    return GatewayConfig(**{"replicas": 1,
                            "socket_dir": str(tmp_path),
                            "credit": 32,
                            "deadline_ms": 60_000.0, **kw}).validate()


# ------------------------------------------------------ protocol codecs

def test_protocol_pair_roundtrip():
    header, arrays = protocol.encode_pairs(7, [(1, 2), (3, 4)],
                                           deadline_ms=500.0, epoch=2)
    fam, payload = protocol.parse_query_frame(
        Frame("q", header, arrays))
    assert fam == "pair"
    assert payload.tolist() == [[1, 2], [3, 4]]
    assert protocol.frame_id(Frame("q", header, arrays)) == 7


def test_protocol_mat_alt_rev_roundtrip():
    h, a = protocol.encode_mat(1, 5, [7, 9, 11])
    fam, (s, targets) = protocol.parse_query_frame(Frame("q", h, a))
    assert (fam, s, targets.tolist()) == ("mat", 5, [7, 9, 11])
    h, a = protocol.encode_alt(2, 5, 9, 3)
    assert protocol.parse_query_frame(Frame("q", h, a)) == (
        "alt", (5, 9, 3))
    h, a = protocol.encode_pairs(3, [(5, 9)], family="rev")
    fam, payload = protocol.parse_query_frame(Frame("q", h, a))
    assert fam == "rev" and payload.tolist() == [[5, 9]]


def test_protocol_unknown_keys_tolerated():
    header, arrays = protocol.encode_pairs(1, [(1, 2)])
    header["shiny_future_field"] = {"nested": True}
    fam, _payload = protocol.parse_query_frame(
        Frame("q", header, arrays))
    assert fam == "pair"


def test_protocol_malformed_raises_typed():
    good_h, good_a = protocol.encode_pairs(1, [(1, 2)])
    bad = [
        Frame("q", {**good_h, "family": "zorp"}, good_a),
        Frame("q", good_h, []),                      # missing payload
        Frame("q", good_h, [np.zeros((2, 3), np.int64)]),  # bad shape
        Frame("q", {"kind": "q", "family": "mat", "id": 1},
              [np.zeros(0, np.int64)]),              # empty targets
        Frame("q", {"kind": "q", "family": "alt", "id": 1}, []),
    ]
    for fr in bad:
        with pytest.raises(GatewayProtocolError):
            protocol.parse_query_frame(fr)
    with pytest.raises(GatewayProtocolError):
        protocol.encode_pairs(1, [1, 2, 3])


def test_hello_gate_newer_tolerate_older():
    protocol.check_hello({"gv": GATEWAY_SCHEMA_VERSION})
    protocol.check_hello({"gv": 0, "unknown": 1})    # older + extras ok
    protocol.check_hello({})                         # no gv = oldest
    with pytest.raises(GatewaySchemaError):
        protocol.check_hello({"gv": GATEWAY_SCHEMA_VERSION + 1})
    fr = Frame("q", {"kind": "q", "family": "pair",
                     "gv": GATEWAY_SCHEMA_VERSION + 1}, [])
    with pytest.raises(GatewaySchemaError):
        protocol.parse_query_frame(fr)


def test_gateway_config_env_degrades(monkeypatch):
    monkeypatch.setenv("DOS_GATEWAY_REPLICAS", "-3")
    monkeypatch.setenv("DOS_GATEWAY_CREDIT", "not-a-number")
    monkeypatch.setenv("DOS_GATEWAY_L2_BYTES", "4096")
    gc = GatewayConfig.from_env()
    assert gc.replicas == GatewayConfig.replicas     # invalid → default
    assert gc.credit == GatewayConfig.credit         # unparseable
    assert gc.l2_bytes == 4096
    assert GatewayConfig.from_env(replicas=5).replicas == 5


# --------------------------------------------------------- server + client

def test_gateway_end_to_end_families(gw_world, tmp_path):
    """All four families over the wire, answers matching the direct
    frontend/planner results; the reply stamps replica identity."""
    conf, g, dc, queries, dispatcher = gw_world
    fe = _frontend(dc, dispatcher)
    fam = QueryFamilies(fe, graph=g)
    srv = GatewayServer(fe, families=fam, fid=0,
                        gconf=_gconf(tmp_path)).start()
    client = None
    try:
        client = DosClient(srv.socket_path)
        assert client.frontend == 0
        pairs = [(int(s), int(t)) for s, t in queries[:8]]
        rows = client.query_batch(pairs, timeout=60.0)
        direct = [fe.submit(s, t).result(60.0) for s, t in pairs]
        assert [(st, c, p, f) for st, c, p, f, _ in rows] == \
            [(r.status, r.cost, r.plen, r.finished) for r in direct]
        s, t = pairs[0]
        # rev == the direct reverse result, labeled with (s, t)
        rrow = client.reverse(s, t, timeout=60.0)
        rres = fam.reverse(s, t).result(60.0).result
        assert rrow[:4] == (rres.status, rres.cost, rres.plen,
                            rres.finished)
        # mat row pinned element-wise against the planner
        targets = [int(q[1]) for q in queries[:6]]
        costs = client.matrix(s, targets, timeout=60.0)
        assert costs == list(fam.matrix(s, targets).result(60.0).costs)
        # alt: ascending (cost, via) alternatives
        alts = client.alternatives(s, t, 3, timeout=60.0)
        assert alts == list(
            fam.alternatives(s, t, 3).result(60.0).alternatives)
        # liveness + statusz surface
        health = client.ping()
        assert health["ok"] and health["frontend"] == 0
        st = srv.statusz()
        assert st["frontend"] == 0 and st["served"] >= 4
    finally:
        if client is not None:
            client.close()
        srv.stop()
        fe.stop()


def test_gateway_malformed_frame_answers_typed_err(gw_world, tmp_path):
    """Satellite pin: a malformed client frame answers a typed err
    frame (never a torn connection), books
    gateway_frames_malformed_total, and the connection keeps serving."""
    conf, g, dc, queries, dispatcher = gw_world
    fe = _frontend(dc, dispatcher)
    srv = GatewayServer(fe, fid=0, gconf=_gconf(tmp_path)).start()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        sock.connect(srv.socket_path)
        reader, writer = FrameReader(sock), FrameWriter(sock)
        assert reader.read().kind == "hello"
        m0 = _counter("gateway_frames_malformed_total")
        h, a = protocol.encode_pairs(4, [(1, 2)])
        writer.send({**h, "family": "zorp"}, a)
        err = reader.read()
        assert err.kind == "err" and "zorp" in err.header["error"]
        assert protocol.frame_id(err) == 4
        assert _counter("gateway_frames_malformed_total") - m0 == 1
        # same connection still serves after the typed refusal
        s, t = int(queries[0][0]), int(queries[0][1])
        h, a = protocol.encode_pairs(5, [(s, t)])
        writer.send(h, a)
        reply = reader.read()
        assert reply.kind == "r" and reply.header["status"] == ["OK"]
        assert srv.statusz()["malformed"] == 1
    finally:
        sock.close()
        srv.stop()
        fe.stop()


def test_gateway_busy_at_credit_window(tmp_path):
    """Query frames past the advertised credit window answer an
    explicit busy frame; the admitted ones still complete."""
    release = threading.Event()
    n = 64

    def slow(wid, q, rconf, diff):
        release.wait(30.0)
        q = np.asarray(q)
        return (np.abs(q[:, 0] - q[:, 1]).astype(np.int64),
                np.ones(len(q), np.int64), np.ones(len(q), bool))

    dc = DistributionController("mod", 1, 1, n)
    fe = _frontend(dc, CallableDispatcher(slow))
    srv = GatewayServer(fe, fid=0,
                        gconf=_gconf(tmp_path, credit=2)).start()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        sock.connect(srv.socket_path)
        reader, writer = FrameReader(sock), FrameWriter(sock)
        assert int(reader.read().header["credit"]) == 2
        b0 = _counter("gateway_busy_total")
        for fid in range(3):
            h, a = protocol.encode_pairs(fid, [(1, 2)])
            writer.send(h, a)
        # release the engine only once the reader has refused the third
        # frame: released earlier, a reply could free a credit before
        # the reader got to it
        deadline = time.monotonic() + 30.0
        while srv.busy < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        release.set()
        kinds = {}
        for _ in range(3):
            fr = reader.read()
            kinds[protocol.frame_id(fr)] = fr.kind
        assert kinds[0] == "r" and kinds[1] == "r"
        assert kinds[2] == "busy"          # third frame over the window
        assert _counter("gateway_busy_total") - b0 == 1
    finally:
        release.set()
        sock.close()
        srv.stop()
        fe.stop()


def test_client_gates_newer_gateway_schema(tmp_path):
    """DosClient refuses a gateway whose hello advertises a NEWER
    schema (gate-newer both directions)."""
    path = str(tmp_path / "fake.sock")
    lsock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    lsock.bind(path)
    lsock.listen(1)

    def fake_gateway():
        conn, _ = lsock.accept()
        FrameWriter(conn).send(
            {"kind": "hello", "gv": GATEWAY_SCHEMA_VERSION + 1,
             "frontend": 0, "credit": 4})
        time.sleep(0.5)
        conn.close()

    th = threading.Thread(target=fake_gateway, daemon=True)
    th.start()
    with pytest.raises(GatewaySchemaError):
        DosClient(path)
    th.join(timeout=5.0)
    lsock.close()


def test_kill_one_frontend_drill(tmp_path):
    """Two replicas, one killed mid-run: every ACCEPTED request is
    answered (the dying replica drains its in-flight frames), and the
    survivor absorbs the rerouted traffic."""
    n = 64

    def answer(wid, q, rconf, diff):
        q = np.asarray(q)
        return (np.abs(q[:, 0] - q[:, 1]).astype(np.int64),
                np.ones(len(q), np.int64), np.ones(len(q), bool))

    dc = DistributionController("mod", 1, 1, n)
    fes = [_frontend(dc, CallableDispatcher(answer)) for _ in range(2)]
    tier = GatewayTier([(fe, None) for fe in fes],
                       gconf=_gconf(tmp_path, replicas=2)).start()
    clients = [DosClient(ep) for ep in tier.endpoints]
    ok_rows = 0
    want = 0
    pool = [[(i % 11 + 1, (i * 7) % 13 + 1) for i in range(8)]
            for _ in range(6)]
    try:
        for batch in pool[:2]:           # both replicas take traffic
            for c in clients:
                rows = c.query_batch(batch, timeout=30.0)
                want += len(batch)
                ok_rows += sum(r[0] == "OK" for r in rows)
        tier.servers[0].stop()           # kill replica 0
        for batch in pool[2:]:
            try:
                rows = clients[0].query_batch(batch, timeout=5.0)
            except (TransportError, GatewayBusy, GatewayError,
                    TimeoutError, OSError):
                # the dead replica refuses cleanly; the client fails
                # over to the survivor — the request is NOT lost
                rows = clients[1].query_batch(batch, timeout=30.0)
            want += len(batch)
            ok_rows += sum(r[0] == "OK" for r in rows)
        assert ok_rows == want           # zero lost accepted requests
        assert tier.statusz()["replicas"] == 2
    finally:
        for c in clients:
            c.close()
        tier.stop()
        for fe in fes:
            fe.stop()


def test_l1_cache_visible_in_statusz(gw_world, tmp_path):
    conf, g, dc, queries, dispatcher = gw_world
    fe = _frontend(dc, dispatcher, cache_bytes=1 << 20)
    srv = GatewayServer(fe, fid=3, gconf=_gconf(tmp_path)).start()
    client = None
    try:
        client = DosClient(srv.socket_path)
        s, t = int(queries[0][0]), int(queries[0][1])
        first = client.query(s, t, timeout=60.0)
        again = client.query(s, t, timeout=60.0)
        assert first[1:4] == again[1:4]
        assert not first[4] and again[4]          # cached flag rides
        st = srv.statusz()
        assert st["l1_hits"] >= 1 and st["l1_hit_rate"] > 0.0
        tier_view = GatewayTier([(fe, None)],
                                gconf=_gconf(tmp_path)).statusz()
        assert tier_view["l1_hit_rate"] >= 0.0
        assert "0" in tier_view["frontends"]
    finally:
        if client is not None:
            client.close()
        srv.stop()
        fe.stop()


# ----------------------------------------------------- worker L2 cache

@pytest.fixture()
def l2_server(gw_world, tmp_path, monkeypatch):
    conf, g, dc, queries, dispatcher = gw_world
    monkeypatch.setenv("DOS_GATEWAY_L2_BYTES", str(1 << 20))
    srv = FifoServer(conf, 0,
                     command_fifo=str(tmp_path / "w0.fifo"))
    assert srv.l2.enabled
    return srv, g, queries


def test_l2_disabled_by_default_keeps_legacy_worker(gw_world, tmp_path,
                                                    monkeypatch):
    """Satellite pin: with DOS_GATEWAY_* unset the worker carries no
    L2 — answer path and statusz are byte-identical pre-gateway."""
    conf, g, dc, queries, dispatcher = gw_world
    monkeypatch.delenv("DOS_GATEWAY_L2_BYTES", raising=False)
    srv = FifoServer(conf, 0, command_fifo=str(tmp_path / "w0.fifo"))
    assert not srv.l2.enabled
    assert "l2" not in srv.statusz()
    rconf = RuntimeConfig()
    h0 = _counter("worker_l2_hits_total")
    m0 = _counter("worker_l2_misses_total")
    c1, p1, f1, _s, _paths = srv.answer_queries(queries[:8], rconf, "-")
    c2, p2, f2, _s, _paths = srv.answer_queries(queries[:8], rconf, "-")
    assert np.array_equal(c1, c2) and np.array_equal(p1, p2)
    assert _counter("worker_l2_hits_total") == h0
    assert _counter("worker_l2_misses_total") == m0


def test_l2_hits_before_kernel(l2_server):
    srv, g, queries = l2_server
    rconf = RuntimeConfig()
    h0 = _counter("worker_l2_hits_total")
    m0 = _counter("worker_l2_misses_total")
    c1, p1, f1, _s, _paths = srv.answer_queries(queries[:8], rconf, "-")
    assert _counter("worker_l2_misses_total") - m0 == 8
    c2, p2, f2, _s, _paths = srv.answer_queries(queries[:8], rconf, "-")
    assert _counter("worker_l2_hits_total") - h0 == 8
    assert np.array_equal(c1, c2) and np.array_equal(p1, p2)
    assert np.array_equal(f1, f2)
    st = srv.statusz()["l2"]
    assert st["entries"] == 8 and st["hits"] >= 8
    # a partial batch: 4 cached + 4 new merge back in query order
    c3, p3, _f, _s, _paths = srv.answer_queries(queries[4:12], rconf,
                                                "-")
    ref_c, ref_p, _rf, _rs, _rp = FifoServer.answer_queries(
        srv, queries[4:12], RuntimeConfig(hscale=rconf.hscale), "-")
    assert np.array_equal(c3, ref_c) and np.array_equal(p3, ref_p)


def test_l2_sig_fabricated_paths_match_engine(l2_server):
    """A sig-requesting caller gets a paths row fabricated from the
    stored signature on a hit — same node set, same move count — or
    the conservative moves=-1 sentinel, never garbage."""
    srv, g, queries = l2_server
    rconf = RuntimeConfig(sig_k=8)
    _c, plen, _f, _s, paths1 = srv.answer_queries(queries[:6], rconf,
                                                  "-")
    _c, _p, _f, _s, paths2 = srv.answer_queries(queries[:6], rconf,
                                                "-")
    assert paths1 is not None and paths2 is not None
    nodes1, moves1 = paths1
    nodes2, moves2 = paths2
    for i in range(6):
        if moves2[i] < 0:
            continue                     # conservative sentinel is ok
        assert moves2[i] == moves1[i]
        assert (set(nodes2[i, :moves2[i] + 1].tolist())
                == set(nodes1[i, :moves1[i] + 1].tolist()))


def test_l2_two_swap_never_serves_stale_cost(l2_server, tmp_path):
    """The PR 9 scoped-invalidation suite at the worker: across TWO
    diff-epoch swaps, an entry whose cached walk touches an updated
    edge always recomputes, a provably-clean survivor re-keys and
    hits — and every answer equals the kernel's own under the active
    fusion."""
    srv, g, queries = l2_server
    srv.traffic = types.SimpleNamespace(scoped_max=10_000)
    srv._l2_prev = (0, "-")
    rconf0 = RuntimeConfig(sig_k=8, diff_epoch=0)
    cost0, _p, fin0, _s, paths = srv.answer_queries(
        queries[:16], rconf0, "-")
    nodes, moves = paths
    # pick A, B: finished walks with disjoint path-node sets, so the
    # swap's affected edge (on A's walk) provably misses B's
    cand = [i for i in range(16) if fin0[i] and moves[i] >= 1]
    a = cand[0]
    a_nodes = set(nodes[a, :moves[a] + 1].tolist())
    b = next(i for i in cand[1:]
             if not (set(nodes[i, :moves[i] + 1].tolist()) & a_nodes))
    b_nodes = set(nodes[b, :moves[b] + 1].tolist())
    edge1 = (int(nodes[a, 0]), int(nodes[a, 1]))    # on A's walk

    fused = {}                           # fused spool is CUMULATIVE

    def swap(depoch, edge, bump):
        fused[edge] = bump
        diff = str(tmp_path / f"fused{depoch}.diff")
        es = list(fused.items())
        write_diff(diff, np.array([e[0][0] for e in es]),
                   np.array([e[0][1] for e in es]),
                   np.array([e[1] for e in es]))
        srv._l2_on_swap(depoch, diff, frozenset({edge}))
        return diff

    diff1 = swap(1, edge1, 10_000)
    rconf1 = RuntimeConfig(sig_k=8, diff_epoch=1)
    h0 = _counter("worker_l2_hits_total")
    got_c, got_p, _f, _s, _paths = srv.answer_queries(
        queries[:16][[a, b]], rconf1, diff1)
    # B survived the swap re-keyed (1 hit), A was dropped and re-ran
    assert _counter("worker_l2_hits_total") - h0 == 1
    ref_c, ref_p, _rf, _rs = srv.engine.answer(
        queries[:16][[a, b]], RuntimeConfig(sig_k=8, diff_epoch=1),
        diff1)
    assert got_c.tolist() == ref_c.tolist()
    assert got_p.tolist() == ref_p.tolist()
    assert got_c[0] != cost0[a]          # the bump priced A's walk up
    assert got_c[1] == cost0[b]          # B untouched by the swap
    # second swap: now B's walk is hit; A's epoch-1 entry must survive
    edge2 = (int(nodes[b, 0]), int(nodes[b, 1]))
    diff2 = swap(2, edge2, 20_000)
    assert srv._l2_prev == (2, diff2)
    rconf2 = RuntimeConfig(sig_k=8, diff_epoch=2)
    h1 = _counter("worker_l2_hits_total")
    got2_c, got2_p, _f, _s, _paths = srv.answer_queries(
        queries[:16][[a, b]], rconf2, diff2)
    assert _counter("worker_l2_hits_total") - h1 == 1   # A re-keyed
    ref2_c, ref2_p, _rf, _rs = srv.engine.answer(
        queries[:16][[a, b]], RuntimeConfig(sig_k=8, diff_epoch=2),
        diff2)
    assert got2_c.tolist() == ref2_c.tolist()
    assert got2_p.tolist() == ref2_p.tolist()
    assert got2_c[1] != cost0[b]         # B re-priced under fusion 2
    # stale-cost regression: nothing ever answered an old epoch's cost
    assert (a_nodes & b_nodes) == set()


def test_l2_flushes_on_membership_commit(l2_server, gw_world):
    """Mid-reshard drill: a committed membership epoch makes every L2
    key unreachable — the cache flushes instead of pinning dead
    entries, and post-commit answers recompute under the new epoch."""
    srv, g, queries = l2_server
    conf = gw_world[0]
    rconf = RuntimeConfig()
    srv.answer_queries(queries[:8], rconf, "-")
    assert len(srv.l2) == 8
    try:
        membership.save_state(conf.outdir, membership.MembershipState(
            epoch=1, workers=["localhost"], owners=[0]))
        srv._refresh_membership()
        assert srv.epoch == 1
        assert len(srv.l2) == 0
        m0 = _counter("worker_l2_misses_total")
        c1, p1, _f, _s, _paths = srv.answer_queries(
            queries[:8], RuntimeConfig(epoch=1), "-")
        assert _counter("worker_l2_misses_total") - m0 == 8
        ref_c, ref_p, _rf, _rs = srv.engine.answer(
            queries[:8], RuntimeConfig(epoch=1), "-")
        assert np.array_equal(c1, ref_c)
        assert np.array_equal(p1, ref_p)
    finally:
        os.remove(membership.state_path(conf.outdir))


def test_l2_bypassed_for_extraction_batches(l2_server):
    """Extraction batches need REAL per-move path prefixes — the L2
    must not intercept them."""
    srv, g, queries = l2_server
    rconf = RuntimeConfig(extract=True, k_moves=4)
    h0 = _counter("worker_l2_hits_total")
    m0 = _counter("worker_l2_misses_total")
    srv.answer_queries(queries[:4], rconf, "-")
    srv.answer_queries(queries[:4], rconf, "-")
    assert _counter("worker_l2_hits_total") == h0
    assert _counter("worker_l2_misses_total") == m0


# --------------------------------------------- control-plane satellite

def test_signal_reader_credit_occupancy():
    from distributed_oracle_search_tpu.control.signals import (
        SignalReader,
    )

    fe = types.SimpleNamespace(statusz=lambda: {
        "transport": {"mode": "rpc", "connections": {
            "0": {"occupancy": 0.25}, "1": {"occupancy": 0.875}}},
        "shards": {},
    })
    sig = SignalReader(frontend=fe).read(now=1.0)
    assert sig.credit_occupancy == {0: 0.25, 1: 0.875}
    assert sig.credit_frac == 0.875
    # a pre-gateway frontend statusz (no transport section) reads clean
    bare = types.SimpleNamespace(statusz=lambda: {"shards": {}})
    sig = SignalReader(frontend=bare).read(now=1.0)
    assert sig.credit_occupancy == {} and sig.credit_frac == 0.0


def test_repair_scaler_trips_on_credit_occupancy():
    from distributed_oracle_search_tpu.control.policy import (
        RepairScaler,
    )
    from distributed_oracle_search_tpu.control.signals import (
        ControlSignals,
    )

    rs = RepairScaler(starve_frac=0.8, hot_frac=0.9, clear_frac=0.5,
                      hold_ticks=2, cooldown_s=0.0)
    # full credit windows with EMPTY frontend queues (the streaming
    # fleet's starvation shape: queues live in the worker)
    sig = ControlSignals(now=0.0, credit_occupancy={0: 0.95},
                         credit_frac=0.95)
    assert rs.decide(sig, 1.0) == []
    assert rs.decide(sig, 2.0) == [("scale_advise",)]
    # neither sensor reporting = no evidence; the rule holds state
    idle = ControlSignals(now=0.0)
    assert rs.decide(idle, 3.0) == []


# --------------------------------------------------- obs-plane satellite

def test_fleet_columns_render_gateway_and_blanks():
    from distributed_oracle_search_tpu.obs import fleet as obs_fleet

    tier_row = obs_fleet._summarize({
        "gateway": {"replicas": 2, "clients": 5, "l1_hit_rate": 0.42},
    })
    assert tier_row["gw"] == "x2" and tier_row["clients"] == 5
    assert tier_row["l1 hit"] == 0.42
    replica_row = obs_fleet._summarize({
        "gateway": {"frontend": 1, "clients": 2, "l1_hit_rate": 0.5},
    })
    assert replica_row["gw"] == "f1"
    worker_row = obs_fleet._summarize({
        "worker": {"batches": 3, "l2": {"hit_rate": 0.75,
                                        "entries": 10}},
    })
    assert worker_row["l2 hit"] == 0.75
    # pre-gateway statusz renders blanks, never a crash
    old = obs_fleet._summarize({"worker": {"batches": 3}})
    assert "gw" not in old and "l2 hit" not in old
    weird = obs_fleet._summarize({
        "gateway": {"replicas": True, "clients": "many",
                    "l1_hit_rate": None},
        "worker": {"l2": {"hit_rate": "hot"}},
    })
    assert "gw" not in weird and "clients" not in weird
    assert "l1 hit" not in weird and "l2 hit" not in weird
    table = obs_fleet.render_top({
        "gw:1": {"gateway": {"replicas": 2, "clients": 5,
                             "l1_hit_rate": 0.42}},
        "old:2": {"worker": {"batches": 3}},
    })
    assert "x2" in table and "-" in table


def test_bench_gateway_keys_pinned():
    """The rush-hour bench keys carry a direction and a tolerance so
    regressions gate instead of drifting silently."""
    from distributed_oracle_search_tpu.obs import fleet as obs_fleet

    keys = {
        "gateway_aggregate_queries_per_sec": "higher",
        "gateway_single_head_queries_per_sec": "higher",
        "gateway_vs_single_head_ratio": "higher",
        "gateway_fairness_ratio": "lower",
        "gateway_answers_match": "higher",
        "gateway_fleet_cache_hit_rate": "higher",
        "gateway_single_head_cache_hit_rate": "higher",
    }
    for key, direction in keys.items():
        assert obs_fleet._KEY_DIRECTIONS.get(key) == direction, key
        assert key in obs_fleet._KEY_TOLERANCES, key
    assert obs_fleet._KEY_TOLERANCES["gateway_answers_match"] == 0.0
