"""TPU ops vs CPU oracle: golden equality on distances, first moves, walks."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_oracle_search_tpu.data import synth_diff
from distributed_oracle_search_tpu.data.graph import Graph, INF
from distributed_oracle_search_tpu.models import (
    dist_to_target, first_move_matrix, table_search_walk,
)
from distributed_oracle_search_tpu.ops import (
    DeviceGraph, dist_to_targets, first_move_from_dist, build_fm_columns,
    table_search_batch,
)


@pytest.fixture(scope="module")
def dg(toy_graph):
    return DeviceGraph.from_graph(toy_graph)


def test_dist_matches_dijkstra(toy_graph, dg):
    g = toy_graph
    targets = np.array([0, 5, g.n // 2, g.n - 1], np.int32)
    dist = np.asarray(dist_to_targets(dg, jnp.asarray(targets)))
    for b, t in enumerate(targets):
        golden = dist_to_target(g, int(t))
        np.testing.assert_array_equal(dist[b], golden)


def test_first_move_matches_oracle_exactly(toy_graph, dg):
    # Equality includes tie-breaking: both sides take the first minimal slot.
    g = toy_graph
    targets = np.arange(g.n, dtype=np.int32)
    fm_tpu = np.asarray(build_fm_columns(dg, jnp.asarray(targets)))
    fm_cpu = first_move_matrix(g, targets)
    np.testing.assert_array_equal(fm_tpu, fm_cpu)


def test_padding_rows_are_inert(toy_graph, dg):
    targets = jnp.asarray([3, -1, 7, -1], jnp.int32)
    dist = dist_to_targets(dg, targets)
    fm = first_move_from_dist(dg, targets, dist)
    assert np.all(np.asarray(dist)[1] == INF)
    assert np.all(np.asarray(fm)[1] == -1)
    assert np.all(np.asarray(fm)[3] == -1)
    # real rows unaffected by the padding rows
    np.testing.assert_array_equal(
        np.asarray(fm)[0], first_move_matrix(toy_graph, np.array([3]))[0])


def test_batch_walk_matches_reference_walk(toy_graph, dg, toy_queries):
    g = toy_graph
    targets = np.arange(g.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))

    s = toy_queries[:, 0].astype(np.int32)
    t = toy_queries[:, 1].astype(np.int32)
    cost, plen, fin = table_search_batch(
        dg, fm, jnp.asarray(t), jnp.asarray(s), jnp.asarray(t), dg.w_pad)
    cost, plen, fin = map(np.asarray, (cost, plen, fin))

    fm_np = np.asarray(fm)
    for i, (si, ti) in enumerate(toy_queries):
        c, p, f, _ = table_search_walk(
            g, lambda x, tt: fm_np[tt, x], int(si), int(ti))
        assert (cost[i], plen[i], fin[i]) == (c, p, f), f"query {si}->{ti}"
        # and the walk cost is the true shortest distance
        assert cost[i] == dist_to_target(g, int(ti))[si]


def test_batch_walk_with_diff(toy_graph, dg, toy_queries):
    g = toy_graph
    w_query = g.weights_with_diff(synth_diff(g, frac=0.3, seed=13))
    w_query_pad = jnp.asarray(g.padded_weights(w_query))
    targets = np.arange(g.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))
    s = jnp.asarray(toy_queries[:, 0], jnp.int32)
    t = jnp.asarray(toy_queries[:, 1], jnp.int32)

    c_free, p_free, f_free = table_search_batch(dg, fm, t, s, t, dg.w_pad)
    c_diff, p_diff, f_diff = table_search_batch(dg, fm, t, s, t, w_query_pad)
    # same routes (free-flow first moves), higher-or-equal cost, same plen
    np.testing.assert_array_equal(np.asarray(p_free), np.asarray(p_diff))
    np.testing.assert_array_equal(np.asarray(f_free), np.asarray(f_diff))
    assert np.all(np.asarray(c_diff) >= np.asarray(c_free))

    fm_np = np.asarray(fm)
    for i in range(0, len(toy_queries), 7):
        si, ti = map(int, toy_queries[i])
        c, p, f, _ = table_search_walk(
            g, lambda x, tt: fm_np[tt, x], si, ti, w_query=w_query)
        assert np.asarray(c_diff)[i] == c


def test_k_moves_budget(toy_graph, dg, toy_queries):
    targets = np.arange(toy_graph.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))
    s = jnp.asarray(toy_queries[:, 0], jnp.int32)
    t = jnp.asarray(toy_queries[:, 1], jnp.int32)
    _, plen_all, fin_all = table_search_batch(dg, fm, t, s, t, dg.w_pad)
    _, plen2, fin2 = table_search_batch(dg, fm, t, s, t, dg.w_pad, k_moves=2)
    # k_moves is a STATIC argname: the unlimited default (-1) compiles a
    # program with NO per-step budget compare — pin that the budgeted
    # lowering is strictly larger, so the specialization cannot silently
    # regress to a traced operand again (advisor r4 found exactly that)
    hlo_unl = table_search_batch.lower(
        dg, fm, t, s, t, dg.w_pad, k_moves=-1).as_text()
    hlo_bud = table_search_batch.lower(
        dg, fm, t, s, t, dg.w_pad, k_moves=2).as_text()
    assert len(hlo_bud) > len(hlo_unl)
    plen_all, fin_all, plen2, fin2 = map(
        np.asarray, (plen_all, fin_all, plen2, fin2))
    assert np.all(plen2 <= 2)
    long_ones = plen_all > 2
    assert not np.any(fin2[long_ones])
    short_ones = (plen_all <= 2) & fin_all
    np.testing.assert_array_equal(fin2[short_ones],
                                  np.ones(short_ones.sum(), bool))


def test_valid_mask_padding(toy_graph, dg):
    targets = np.arange(toy_graph.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))
    s = jnp.asarray([1, 0, 2], jnp.int32)
    t = jnp.asarray([5, 0, 9], jnp.int32)
    valid = jnp.asarray([True, False, True])
    cost, plen, fin = table_search_batch(dg, fm, t, s, t, dg.w_pad, valid=valid)
    assert not np.asarray(fin)[1] and np.asarray(cost)[1] == 0
    assert np.asarray(fin)[0] and np.asarray(fin)[2]


def test_unreachable_batch():
    g = Graph(xs=[0, 1, 5, 6], ys=[0, 0, 0, 0],
              src=[0, 1, 2, 3], dst=[1, 0, 3, 2], w=[1, 1, 1, 1])
    dg = DeviceGraph.from_graph(g)
    fm = build_fm_columns(dg, jnp.asarray([3], jnp.int32))
    cost, plen, fin = table_search_batch(
        dg, fm, jnp.asarray([0]), jnp.asarray([0]), jnp.asarray([3]),
        dg.w_pad)
    assert not np.asarray(fin)[0] and np.asarray(plen)[0] == 0


def test_bucketed_walk_invariant(toy_graph, dg, toy_queries):
    """n_buckets must never change answers — same results for 1, explicit
    B, auto, with k_moves budgets and valid padding, odd batch sizes."""
    from distributed_oracle_search_tpu.ops.table_search import pick_buckets

    g = toy_graph
    targets = np.arange(g.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))
    # replicate queries to a biggish batch with an odd size
    q = np.tile(toy_queries, (41, 1))[:257]
    s = jnp.asarray(q[:, 0], jnp.int32)
    t = jnp.asarray(q[:, 1], jnp.int32)
    valid = jnp.asarray(np.arange(len(q)) % 5 != 3)
    for k_moves in (-1, 2):
        ref = table_search_batch(dg, fm, t, s, t, dg.w_pad, valid=valid,
                                 k_moves=k_moves, n_buckets=1)
        for b in (0, 2, 4, 16):
            got = table_search_batch(dg, fm, t, s, t, dg.w_pad,
                                     valid=valid, k_moves=k_moves,
                                     n_buckets=b)
            for a, r in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    # odd sizes fall back to a divisor (257 is prime -> 1 bucket)
    assert pick_buckets(257, 0) == 1
    assert pick_buckets(65536, 0) == 64
    assert pick_buckets(8192, 0) == 8
    assert pick_buckets(100, 6) == 5


def test_multi_diff_fused_walk_matches_sequential(toy_graph, dg,
                                                  toy_queries):
    """One fused walk under D diffs must equal D sequential single-diff
    walks exactly — costs per diff, shared plen/finished — across bucket
    counts and with valid padding."""
    from distributed_oracle_search_tpu.data import synth_diff
    from distributed_oracle_search_tpu.ops.table_search import (
        table_search_multi,
    )

    g = toy_graph
    targets = np.arange(g.n, dtype=np.int32)
    fm = build_fm_columns(dg, jnp.asarray(targets))
    q = np.tile(toy_queries, (23, 1))[:144]
    s = jnp.asarray(q[:, 0], jnp.int32)
    t = jnp.asarray(q[:, 1], jnp.int32)
    valid = jnp.asarray(np.arange(len(q)) % 7 != 2)
    w_list = [None,
              g.weights_with_diff(synth_diff(g, frac=0.3, seed=11)),
              g.weights_with_diff(synth_diff(g, frac=0.5, seed=12))]
    w_pads = jnp.asarray(np.stack([
        g.padded_weights(g.w if w is None else w) for w in w_list]),
        jnp.int32)
    for b in (0, 1, 4):
        cost, plen, fin = table_search_multi(dg, fm, t, s, t, w_pads,
                                             valid=valid, n_buckets=b)
        assert cost.shape == (3, len(q))
        for di, w in enumerate(w_list):
            wp = dg.w_pad if w is None else jnp.asarray(
                g.padded_weights(w), jnp.int32)
            c1, p1, f1 = table_search_batch(dg, fm, t, s, t, wp,
                                            valid=valid, n_buckets=b)
            np.testing.assert_array_equal(np.asarray(cost[di]),
                                          np.asarray(c1))
            np.testing.assert_array_equal(np.asarray(plen),
                                          np.asarray(p1))
            np.testing.assert_array_equal(np.asarray(fin),
                                          np.asarray(f1))
    # max_steps truncates EXACTLY like the single-diff kernel
    # (regression: the while cond alone overshot by up to unroll-1)
    cm, pm, fmm = table_search_multi(dg, fm, t, s, t, w_pads,
                                     valid=valid, max_steps=3)
    c3, p3, f3 = table_search_batch(dg, fm, t, s, t, w_pads[0],
                                    valid=valid, max_steps=3)
    assert int(np.asarray(pm).max()) <= 3
    np.testing.assert_array_equal(np.asarray(cm[0]), np.asarray(c3))
    np.testing.assert_array_equal(np.asarray(pm), np.asarray(p3))
    np.testing.assert_array_equal(np.asarray(fmm), np.asarray(f3))


def _all_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (jit,
    scan, while, cond)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _all_eqns(sub)


@pytest.mark.parametrize("kernel", ["batch", "multi"])
def test_walk_gathers_table_in_place(toy_graph, dg, toy_queries, kernel):
    """The walk reads the first-move table in its resident [R, N]
    layout: no reshape of the table operand (on the TPU a flattening
    reshape copies the whole table every call), and its gathers read the
    table itself."""
    from distributed_oracle_search_tpu.ops.table_search import (
        table_search_multi,
    )

    g = toy_graph
    fm = build_fm_columns(dg, jnp.arange(5, dtype=jnp.int32))  # [5, N]
    s = jnp.asarray(toy_queries[:, 0], jnp.int32)
    t = jnp.asarray(toy_queries[:, 1] % 5, jnp.int32)
    if kernel == "batch":
        jaxpr = jax.make_jaxpr(table_search_batch)(dg, fm, t, s, t,
                                                   dg.w_pad)
    else:
        w_pads = jnp.stack([dg.w_pad, dg.w_pad])
        jaxpr = jax.make_jaxpr(table_search_multi)(dg, fm, t, s, t,
                                                   w_pads)

    def reads_table(eqn):
        a = eqn.invars[0].aval
        return a.shape == (5, g.n) and a.dtype == jnp.int8

    eqns = list(_all_eqns(jaxpr.jaxpr))
    assert not [e for e in eqns
                if e.primitive.name == "reshape" and reads_table(e)]
    assert [e for e in eqns
            if e.primitive.name == "gather" and reads_table(e)]


def test_route_sorts_by_length_estimate(toy_graph):
    """route() orders each worker group by the coordinate-distance
    estimate (slot_q ascends with expected walk length) and still
    scatters answers back to input order."""
    from distributed_oracle_search_tpu.models.cpd import CPDOracle
    from distributed_oracle_search_tpu.parallel import (
        DistributionController,
    )
    from distributed_oracle_search_tpu.parallel.mesh import make_mesh

    g = toy_graph
    dc = DistributionController("mod", 1, 1, g.n)
    o = CPDOracle(g, dc, mesh=make_mesh(n_workers=1))
    rng = np.random.default_rng(0)
    q = np.stack([rng.integers(0, g.n, 64), rng.integers(0, g.n, 64)],
                 axis=1)
    r_arr, s_arr, t_arr, valid, scatter = o.route(q)
    est = o._length_estimate(q)
    active, sd, sw, sq = scatter
    # same (d) lane: higher slot_q => est must not decrease
    for d in range(r_arr.shape[0]):
        lane = np.nonzero((sd == d) & active)[0]
        order = np.argsort(sq[lane])
        assert (np.diff(est[lane][order]) >= 0).all()
