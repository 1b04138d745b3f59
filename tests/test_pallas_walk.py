"""Pallas-fused walk kernel: parity + selection suite.

The fused kernel (``ops.pallas_walk``) must answer exactly like the
two references it shadows: element-wise equal to the CPU oracle
(``models.reference.table_search_walk``) and BIT-identical to the XLA
walk (``ops.table_search.table_search_batch``). Everything here runs
the kernel in Pallas interpret mode so the whole suite executes in the
CPU tier-1 run; the compiled real-chip run sits behind ``slow``.
``conftest.py`` pins ``DOS_WALK_KERNEL=xla`` for the rest of the suite
— these tests opt into pallas explicitly, so the fused path cannot
silently stop being exercised on CPU-only containers.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_oracle_search_tpu.data import synth_diff, synth_scenario
from distributed_oracle_search_tpu.data.graph import Graph
from distributed_oracle_search_tpu.models import table_search_walk
from distributed_oracle_search_tpu.models.cpd import build_worker_shard
from distributed_oracle_search_tpu.obs import fleet
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.ops import (
    DeviceGraph, build_fm_columns, pallas_walk_batch, pallas_walk_fits,
    resolve_walk_kernel, table_search_batch,
)
from distributed_oracle_search_tpu.ops import pallas_walk as pw
from distributed_oracle_search_tpu.ops.pallas_walk import (
    WalkKernelUnavailable, choose_walk_kernel,
)
from distributed_oracle_search_tpu.ops.table_search import (
    BUCKET_MAX, pick_buckets,
)
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.worker.engine import ShardEngine


@pytest.fixture(scope="module")
def dg(toy_graph):
    return DeviceGraph.from_graph(toy_graph)


@pytest.fixture(scope="module")
def fm(toy_graph, dg):
    targets = np.arange(toy_graph.n, dtype=np.int32)
    return build_fm_columns(dg, jnp.asarray(targets))


@pytest.fixture(scope="module")
def walk_queries(toy_graph, toy_queries):
    """The scenario plus the awkward rows: zero-length (s==t) and
    duplicate pairs."""
    q = np.asarray(toy_queries, np.int64)
    extra = np.array([[3, 3], [0, 0],              # zero-length
                      q[0].tolist(), q[0].tolist(),  # duplicates
                      q[5].tolist()], np.int64)
    return np.concatenate([q, extra], axis=0)


def _both_kernels(dg, fm, queries, w_pad, **kw):
    """Run XLA and Pallas (interpret) on identical inputs."""
    s = jnp.asarray(queries[:, 0], jnp.int32)
    t = jnp.asarray(queries[:, 1], jnp.int32)
    rows = jnp.asarray(queries[:, 1], jnp.int32)
    a = table_search_batch(dg, fm, rows, s, t, w_pad, **kw)
    b = pallas_walk_batch(dg, fm, rows, s, t, w_pad, **kw)
    return a, b


def _assert_bit_identical(a, b):
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------- pick_buckets edges

@pytest.mark.parametrize("q", [0, 1, 2, 3, 7, 97, 4099, 9973, 65536])
@pytest.mark.parametrize("n_buckets", [0, 1, 3, 64, 1000])
def test_pick_buckets_never_zero_never_uneven(q, n_buckets):
    """The kernel's grid resolver: q=0 and prime q must degrade to 1,
    never return 0 or a non-divisor (a 0 grid or ragged bucket would
    fault the pallas_call)."""
    b = pick_buckets(q, n_buckets)
    assert b >= 1
    if q > 0:
        assert q % b == 0
        assert b <= max(q, 1)


def test_pick_buckets_prime_degrades_to_one():
    for prime in (4099, 9973):
        assert pick_buckets(prime, 0) == 1
        assert pick_buckets(prime, 7) == 1


def test_pick_buckets_auto_cap():
    assert pick_buckets(1 << 20, 0) == BUCKET_MAX


# ------------------------------------------------------ kernel parity

def test_parity_vs_cpu_reference(toy_graph, dg, fm, walk_queries):
    """Element-wise vs models.reference.table_search_walk, free-flow
    and diffed — moves on free-flow first moves, costs on query-time
    weights."""
    g = toy_graph
    fm_np = np.asarray(fm)
    w_diff = g.weights_with_diff(synth_diff(g, frac=0.2, seed=3))
    for w_query in (None, w_diff):
        w_pad = jnp.asarray(g.padded_weights(w_query), jnp.int32)
        s = jnp.asarray(walk_queries[:, 0], jnp.int32)
        t = jnp.asarray(walk_queries[:, 1], jnp.int32)
        rows = jnp.asarray(walk_queries[:, 1], jnp.int32)
        cost, plen, fin = pallas_walk_batch(dg, fm, rows, s, t, w_pad)
        for i, (sq, tq) in enumerate(walk_queries):
            c, p, f, _ = table_search_walk(
                g, lambda x, tt: fm_np[tt, x], int(sq), int(tq),
                w_query=w_query)
            assert (int(cost[i]), int(plen[i]), bool(fin[i])) == \
                (c, p, f), f"query {i} ({sq}->{tq})"


@pytest.mark.parametrize("k_moves", [-1, 0, 1, 3])
@pytest.mark.parametrize("n_buckets", [0, 1, 2, 4])
def test_bit_identical_vs_xla(toy_graph, dg, fm, walk_queries,
                              k_moves, n_buckets):
    g = toy_graph
    w_diff = g.weights_with_diff(synth_diff(g, frac=0.2, seed=3))
    for w in (dg.w_pad, jnp.asarray(g.padded_weights(w_diff),
                                    jnp.int32)):
        a, b = _both_kernels(dg, fm, walk_queries, w,
                             k_moves=k_moves, n_buckets=n_buckets)
        _assert_bit_identical(a, b)


def test_bit_identical_with_pad_lanes_and_max_steps(dg, fm,
                                                    walk_queries):
    nq = len(walk_queries)
    valid = np.ones(nq, bool)
    valid[nq - 6:] = False
    a, b = _both_kernels(dg, fm, walk_queries, dg.w_pad,
                         valid=jnp.asarray(valid), max_steps=5)
    _assert_bit_identical(a, b)
    # pad lanes come back zero / unfinished from BOTH kernels
    for arr in (a[0], a[1], a[2], b[0], b[1], b[2]):
        assert not np.asarray(arr)[nq - 6:].any()


def test_k_moves_budget_exhaustion(toy_graph, dg, fm):
    """A budget smaller than the walk truncates at EXACTLY k moves,
    unfinished — pinned against the reference and the XLA path."""
    g = toy_graph
    fm_np = np.asarray(fm)
    # corner-to-corner queries are longer than 2 moves on an 8x6 grid
    queries = np.array([[0, g.n - 1], [g.n - 1, 0], [1, g.n - 2],
                        [2, 2]], np.int64)
    a, b = _both_kernels(dg, fm, queries, dg.w_pad, k_moves=2)
    _assert_bit_identical(a, b)
    cost, plen, fin = b
    for i, (sq, tq) in enumerate(queries):
        c, p, f, _ = table_search_walk(
            g, lambda x, tt: fm_np[tt, x], int(sq), int(tq), k_moves=2)
        assert (int(cost[i]), int(plen[i]), bool(fin[i])) == (c, p, f)
    assert int(plen[0]) == 2 and not bool(fin[0])
    assert bool(fin[3]) and int(plen[3]) == 0      # s==t inside budget


def test_unreachable_minus_one_rows():
    """Two directed 4-cycles, no edges between them: cross-component
    queries sit on -1 first-move rows and must halt at birth."""
    n = 8
    src = np.array([0, 1, 2, 3, 4, 5, 6, 7])
    dst = np.array([1, 2, 3, 0, 5, 6, 7, 4])
    w = np.full(8, 10, np.int32)
    g = Graph(np.arange(n), np.zeros(n), src, dst, w)
    dg2 = DeviceGraph.from_graph(g)
    fm2 = build_fm_columns(dg2, jnp.asarray(np.arange(n, dtype=np.int32)))
    fm_np = np.asarray(fm2)
    assert (fm_np[0, 4:] == -1).all()      # cross-component rows
    queries = np.array([[0, 5], [6, 2], [0, 3], [4, 7], [5, 5]],
                       np.int64)
    a, b = _both_kernels(dg2, fm2, queries, dg2.w_pad)
    _assert_bit_identical(a, b)
    cost, plen, fin = b
    for i, (sq, tq) in enumerate(queries):
        c, p, f, _ = table_search_walk(
            g, lambda x, tt: fm_np[tt, x], int(sq), int(tq))
        assert (int(cost[i]), int(plen[i]), bool(fin[i])) == (c, p, f)
    assert not bool(fin[0]) and int(plen[0]) == 0   # unreachable
    assert bool(fin[2]) and int(cost[2]) == 30      # in-component


def test_empty_batch():
    g = Graph(np.arange(2), np.zeros(2), [0, 1], [1, 0], [1, 1])
    dg2 = DeviceGraph.from_graph(g)
    fm2 = build_fm_columns(dg2, jnp.asarray(np.arange(2, dtype=np.int32)))
    z = np.zeros((0,), np.int32)
    cost, plen, fin = pallas_walk_batch(
        dg2, fm2, jnp.asarray(z), jnp.asarray(z), jnp.asarray(z),
        dg2.w_pad)
    assert cost.shape == plen.shape == fin.shape == (0,)


# ------------------------------------------------- knob + fit policy

def test_conftest_pins_xla_for_tier1():
    """The suite-wide default is the XLA reference path; this file's
    pallas coverage is explicit opt-in (the pin is what keeps a
    container env from flipping the whole tier-1 run to interpret
    speed)."""
    assert os.environ.get("DOS_WALK_KERNEL") == "xla"
    assert resolve_walk_kernel() == "xla"


@pytest.mark.parametrize("backend", ("cpu", "tpu"))
def test_knob_resolution(monkeypatch, backend):
    """``auto`` is the XLA walk on every backend (the TPU compiler
    refuses the fused kernel); only an explicit request picks Pallas."""
    monkeypatch.setattr(pw.jax, "default_backend", lambda: backend)
    monkeypatch.setenv("DOS_WALK_KERNEL", "auto")
    assert resolve_walk_kernel() == "xla"
    monkeypatch.setenv("DOS_WALK_KERNEL", "pallas")
    assert resolve_walk_kernel() == "pallas"
    monkeypatch.setenv("DOS_WALK_KERNEL", "XLA")       # case-tolerant
    assert resolve_walk_kernel() == "xla"
    monkeypatch.setenv("DOS_WALK_KERNEL", "bogus")     # degrade, not crash
    assert resolve_walk_kernel() == "xla"


def test_explicit_pallas_refused_on_tpu_with_compiler_reason(monkeypatch):
    """On a TPU an explicit pallas request raises with the compiler's
    reason instead of serving the XLA walk."""
    monkeypatch.setattr(pw.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DOS_WALK_KERNEL", "auto")
    assert choose_walk_kernel(48, 4, 164, 64) == "xla"
    monkeypatch.setenv("DOS_WALK_KERNEL", "pallas")
    with pytest.raises(WalkKernelUnavailable, match="gather lowering"):
        choose_walk_kernel(48, 4, 164, 64)


def test_vmem_fit_check(monkeypatch):
    ok, why = pallas_walk_fits(48, 4, 164, 1024)
    assert ok and why == ""
    ok, why = pallas_walk_fits(5_000_000, 8, 20_000_000, 65536)
    assert not ok and "VMEM budget" in why
    monkeypatch.setenv("DOS_WALK_VMEM_MB", "0.001")
    ok, why = pallas_walk_fits(48, 4, 164, 1024)
    assert not ok
    monkeypatch.setenv("DOS_WALK_VMEM_MB", "junk")     # degrade to default
    ok, _ = pallas_walk_fits(48, 4, 164, 1024)
    assert ok
    assert pallas_walk_fits(48, 4, 164, 0)[0]          # empty batch


# ------------------------------------------- engine dedup/unsort path

@pytest.fixture(scope="module")
def shard_setup(toy_graph, tmp_path_factory):
    outdir = str(tmp_path_factory.mktemp("pallas-shard"))
    dc = DistributionController("mod", 2, 2, toy_graph.n)
    build_worker_shard(toy_graph, dc, 0, outdir, chunk=16)
    return dc, outdir


def _engine_config():
    from distributed_oracle_search_tpu.cli import process_query as pq
    from distributed_oracle_search_tpu.cli.args import parse_args
    return pq.runtime_config(parse_args([]))


def test_engine_duplicates_unsort_pallas(toy_graph, shard_setup,
                                         monkeypatch):
    """The fused kernel through ShardEngine's dedup/unsort machinery:
    duplicate (s, t) pairs, zero-length queries, answers element-wise
    equal to the CPU reference AND bit-identical to the XLA engine,
    with the pallas selection booked on its counter."""
    g = toy_graph
    dc, outdir = shard_setup
    rng = np.random.default_rng(5)
    nodes = np.arange(g.n)
    owned0 = nodes[dc.worker_of(nodes) == 0]
    t = rng.choice(owned0, 24)
    s = rng.choice(nodes, 24)
    queries = np.stack([s, t], axis=1).astype(np.int64)
    queries[3] = queries[0]                     # duplicates
    queries[7] = queries[0]
    queries[9] = (queries[9][1], queries[9][1])  # zero-length s==t
    config = _engine_config()

    monkeypatch.setenv("DOS_WALK_KERNEL", "xla")
    eng_x = ShardEngine(g, dc, wid=0, outdir=outdir)
    cost_x, plen_x, fin_x, stats_x = eng_x.answer(queries, config)

    snap0 = obs_metrics.REGISTRY.snapshot()["counters"]
    monkeypatch.setenv("DOS_WALK_KERNEL", "pallas")
    eng_p = ShardEngine(g, dc, wid=0, outdir=outdir)
    cost_p, plen_p, fin_p, stats_p = eng_p.answer(queries, config)
    snap1 = obs_metrics.REGISTRY.snapshot()["counters"]
    assert snap1.get("walk_pallas_batches_total", 0) \
        == snap0.get("walk_pallas_batches_total", 0) + 1

    _assert_bit_identical((cost_x, plen_x, fin_x),
                          (cost_p, plen_p, fin_p))
    assert fin_p.all()
    # stats count per ORIGINAL query, duplicates included
    assert stats_p.finished == len(queries) == stats_x.finished
    fm_np = np.asarray(eng_p.fm)
    rows = dc.owned_index_of(queries[:, 1])
    for i, (sq, tq) in enumerate(queries):
        c, p, f, _ = table_search_walk(
            g, lambda x, tt, r=rows[i]: fm_np[r, x], int(sq), int(tq))
        assert (int(cost_p[i]), int(plen_p[i]), bool(fin_p[i])) == \
            (c, p, f)
    # duplicates fanned back out identically
    assert cost_p[3] == cost_p[0] == cost_p[7]
    assert plen_p[9] == 0 and fin_p[9]


def test_engine_diffed_weights_pallas(toy_graph, shard_setup, tmp_path,
                                      monkeypatch):
    """Diff applied at query time through the fused kernel: moves stay
    free-flow, costs dominate free flow, bit-identical to XLA."""
    from distributed_oracle_search_tpu.data.formats import write_diff

    g = toy_graph
    dc, outdir = shard_setup
    dsrc, ddst, dw = synth_diff(g, frac=0.3, seed=9)
    difffile = str(tmp_path / "q.diff")
    write_diff(difffile, dsrc, ddst, dw)
    nodes = np.arange(g.n)
    owned0 = nodes[dc.worker_of(nodes) == 0]
    queries = np.stack([nodes[:16], np.resize(owned0, 16)],
                       axis=1).astype(np.int64)
    config = _engine_config()

    monkeypatch.setenv("DOS_WALK_KERNEL", "pallas")
    eng_p = ShardEngine(g, dc, wid=0, outdir=outdir)
    free = eng_p.answer(queries, config)
    diffed = eng_p.answer(queries, config, difffile=difffile)
    monkeypatch.setenv("DOS_WALK_KERNEL", "xla")
    eng_x = ShardEngine(g, dc, wid=0, outdir=outdir)
    diffed_x = eng_x.answer(queries, config, difffile=difffile)
    _assert_bit_identical(diffed[:3], diffed_x[:3])
    assert (diffed[0] >= free[0]).all()          # diff only raises cost
    assert (diffed[1] == free[1]).all()          # trajectory unchanged


def test_engine_vmem_overrun_refuses_pallas(toy_graph, shard_setup,
                                           monkeypatch):
    """A pallas-requested batch over the VMEM budget is refused with
    the reason, books no batch on either kernel, and the same engine
    still answers once the request is withdrawn."""
    g = toy_graph
    dc, outdir = shard_setup
    nodes = np.arange(g.n)
    owned0 = nodes[dc.worker_of(nodes) == 0]
    queries = np.stack([nodes[:8], np.resize(owned0, 8)],
                       axis=1).astype(np.int64)
    monkeypatch.setenv("DOS_WALK_KERNEL", "pallas")
    monkeypatch.setenv("DOS_WALK_VMEM_MB", "0.0001")
    snap0 = obs_metrics.REGISTRY.snapshot()["counters"]
    eng = ShardEngine(g, dc, wid=0, outdir=outdir)
    with pytest.raises(WalkKernelUnavailable, match="VMEM budget"):
        eng.answer(queries, _engine_config())
    snap1 = obs_metrics.REGISTRY.snapshot()["counters"]
    for name in ("walk_xla_batches_total", "walk_pallas_batches_total"):
        assert snap1.get(name, 0) == snap0.get(name, 0)
    monkeypatch.setenv("DOS_WALK_KERNEL", "auto")
    assert eng.answer(queries, _engine_config())[2].all()


# ------------------------------------------------- bench-diff gate

def _bench_record(tmp_path, name, headline):
    p = tmp_path / name
    p.write_text(json.dumps({
        "metric": "scenario_queries_per_sec", "value": 100000.0,
        "headline": headline}))
    return str(p)


def test_bench_diff_knows_walk_key_directions(tmp_path):
    """walk_* headline keys gate with the right direction: q/s and
    lane fractions are higher-is-better (a drop regresses), stall is
    lower-is-better (a rise regresses), and the lane fraction uses the
    tighter per-key tolerance."""
    old = _bench_record(tmp_path, "BENCH_r01.json", {
        "walk_pallas_queries_per_sec": 500000.0,
        "walk_pallas_stall_p99_ms": 2.0,
        "walk_useful_lane_fraction": 0.5,
        "walk_pallas_speedup": 2.0,
    })
    bad = _bench_record(tmp_path, "BENCH_r02.json", {
        "walk_pallas_queries_per_sec": 200000.0,   # drop: regression
        "walk_pallas_stall_p99_ms": 9.0,           # rise: regression
        "walk_useful_lane_fraction": 0.4,          # -20% > 15% tol
        "walk_pallas_speedup": 2.1,
    })
    out = fleet.compare_bench(old, bad)
    by_key = {e["key"]: e for e in out["regressions"]}
    assert by_key["walk_pallas_queries_per_sec"]["direction"] == "higher"
    assert by_key["walk_pallas_stall_p99_ms"]["direction"] == "lower"
    assert by_key["walk_useful_lane_fraction"]["tolerance"] == \
        pytest.approx(0.15)
    assert "walk_pallas_speedup" not in by_key

    ok = _bench_record(tmp_path, "BENCH_r03.json", {
        "walk_pallas_queries_per_sec": 520000.0,
        "walk_pallas_stall_p99_ms": 1.5,
        "walk_useful_lane_fraction": 0.47,         # -6%: inside tol
        "walk_pallas_speedup": 2.4,
    })
    out = fleet.compare_bench(old, ok)
    assert out["regressions"] == []


# --------------------------------------------------- real chip (slow)

@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled fused kernel needs a real TPU")
def test_compiled_kernel_parity_on_tpu(toy_graph, dg, fm, walk_queries):
    """interpret=False: the Mosaic-compiled kernel (double-buffered DMA
    loader) against the XLA walk on hardware."""
    s = jnp.asarray(walk_queries[:, 0], jnp.int32)
    t = jnp.asarray(walk_queries[:, 1], jnp.int32)
    rows = jnp.asarray(walk_queries[:, 1], jnp.int32)
    a = table_search_batch(dg, fm, rows, s, t, dg.w_pad)
    b = pallas_walk_batch(dg, fm, rows, s, t, dg.w_pad,
                          interpret=False)
    _assert_bit_identical(a, b)
