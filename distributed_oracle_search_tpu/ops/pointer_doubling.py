"""Pointer-doubling: whole-shard path costs in O(log L) sweeps.

The framework's "long-context" machinery (SURVEY.md §5): a table-search
walk is a sequential chain of up to L = max-path-length dependent gathers —
the structural analog of a long sequence. Instead of walking each query,
**double the successor function**: with

    S_0[r, x] = next node on the CPD path from x toward target r
    C_0[r, x] = query-time cost of that one move

repeated squaring

    S_{k+1}[r, x] = S_k[r, S_k[r, x]]
    C_{k+1}[r, x] = C_k[r, x] + C_k[r, S_k[r, x]]

converges in ceil(log2 L) sweeps to the TOTAL cost from every node to
every owned target — after which any (s, t) query is ONE gather, on diffed
weights too (the walk's only advantage was laziness).

Cost model — MEASURED, not aspirational, and regenerated every bench run
(bench graph 9216x9216, v5e, captured in the driver's BENCH artifacts —
the ``table_breakeven_queries`` field is computed from the same run's
prepare/walk/lookup timings, never quoted from memory): one sweep is ONE
packed dependent ``[R, N]`` gather (succ, cost, plen as 12 adjacent
bytes) — ~**19 s** prepare for the full shard, then lookups at ~320-520k
q/s vs the ~200-310k q/s diffed walk (r04 captures, individual runs
±20%). Break-even
(``prepare / (1/walk_qps − 1/lookup_qps)``) divides by the small
walk-vs-lookup gap, so captures range ~**9-34M queries** per diff round
before the tables pay for themselves — every point in that band is the
regime of BASELINE.md configs[4]'s 10M-query DIMACS campaign, not of
small scenarios. ``doubled_tables_multi`` changes the arithmetic
D-fold: the fused sweep prepares D diffs' tables for ~one prepare
(measured 4 diffs in 16.5 s vs 18.8 s for one — the sweep is
lane-bound, not byte-bound), dividing the per-diff break-even by ~D. Memory:
cost int32 + sign-packed plen (int16 when ``N < 32768``) = 6-8 bytes per
entry = **6-8x the fm shard**; ``models.cpd.prepare_weights`` enforces a
budget gate before allocating.
Self-loops make the recursion total: the target itself and stuck
(unreachable) nodes point at themselves with step cost 0, so their
accumulated cost is exactly the walk's cost-until-stuck.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .device_graph import DeviceGraph


def plen_dtype(n: int):
    """Packed-plen dtype: int16 when every path length (< N) fits with
    the sign bit spare, else int32."""
    return jnp.int16 if n < (1 << 15) else jnp.int32


@functools.partial(jax.jit, static_argnames=("max_len",))
def doubled_tables(dg: DeviceGraph, fm: jnp.ndarray, targets: jnp.ndarray,
                   w_query_pad: jnp.ndarray, max_len: int = 0):
    """All-source cost + packed-plen tables for one fm shard.

    Parameters
    ----------
    fm          : int8 [R, N] first-move rows (free-flow moves)
    targets     : int32 [R] global node id of each row's target (-1 pad)
    w_query_pad : int32 [M+1] query-time weights (diff applied)
    max_len     : path-length bound (0 = N, the simple-path bound)

    Returns
    -------
    cost [R, N] int32, plen_packed [R, N] (:func:`plen_dtype`):
    ``finished`` rides plen's sign — finished entries store ``plen``,
    unfinished store ``-plen - 1`` (decode via :func:`lookup_tables`).
    Rows with ``targets[r] < 0`` are all-unfinished padding. Dropping the
    separate finished tensor and narrowing plen cuts the table from 12 to
    6-8 bytes per entry.
    """
    r, n = fm.shape
    limit = n if max_len == 0 else max_len
    rows = jnp.arange(r, dtype=jnp.int32)[:, None]
    x = jnp.arange(n, dtype=jnp.int32)[None, :]

    slot = fm.astype(jnp.int32)
    can = slot >= 0
    slot_safe = jnp.maximum(slot, 0)
    eid = dg.out_eid[x.repeat(r, 0), slot_safe]
    nxt = dg.out_nbr[x.repeat(r, 0), slot_safe]
    succ = jnp.where(can, nxt, x)                  # self-loop when stuck
    cost = jnp.where(can, w_query_pad[eid], 0)
    plen = jnp.where(can, 1, 0).astype(jnp.int32)

    n_sweeps = max(int(limit - 1).bit_length(), 1)

    def cond(state):
        i, _, _, _, changed = state
        return changed & (i < n_sweeps)

    def body(state):
        i, succ, cost, plen, _ = state
        # (succ, cost, plen) share the gather indices: pack them as three
        # adjacent int32s so ONE take_along_axis (12 contiguous bytes per
        # lane) replaces three separate gathers — measured 2.1x on the
        # bench shard's prepare
        packed = jnp.stack([succ, cost, plen], axis=-1)
        gat = jnp.take_along_axis(packed, succ[..., None], axis=1)
        new_succ = gat[..., 0]
        cost = cost + gat[..., 1]
        plen = plen + gat[..., 2]
        # converged once every chain reached its fixed point: the sweep
        # count then adapts to log2(actual max path length), not log2(N)
        return i + 1, new_succ, cost, plen, jnp.any(new_succ != succ)

    # Seed `changed` from the data (True iff some chain is not yet at its
    # fixed point) rather than the literal True: under shard_map the body's
    # jnp.any(...) output is varying over the worker axis, so the initial
    # carry must be varying too or tracing rejects the loop.
    changed0 = jnp.any(succ != x)
    _, succ, cost, plen, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), succ, cost, plen, changed0))

    valid = targets >= 0
    t_safe = jnp.where(valid, targets, 0).astype(jnp.int32)
    finished = (succ == t_safe[:, None]) & valid[:, None]
    del rows
    plen_packed = jnp.where(finished, plen, -plen - 1).astype(plen_dtype(n))
    return cost, plen_packed


@functools.partial(jax.jit, static_argnames=("max_len",))
def doubled_tables_multi(dg: DeviceGraph, fm: jnp.ndarray,
                         targets: jnp.ndarray, w_pads: jnp.ndarray,
                         max_len: int = 0):
    """All-source cost tables for one fm shard under D diffs at once.

    The successor function is diff-independent (free-flow moves), so
    the doubling recursion is shared: one fused sweep squares ``succ``
    and accumulates EVERY diff's costs with a single
    ``jnp.take_along_axis`` of ``(2 + D)`` adjacent int32s per lane —
    preparing D diff rounds' tables for ~the price of one (the sweep is
    gather-bound; only the payload widens). ``w_pads``: int32
    ``[D, M+1]``, one padded weight row per diff.

    Returns ``(costs [R, N, D] int32, plen_packed [R, N])`` —
    ``plen``/``finished`` ride one shared sign-packed array because the
    trajectory is shared (:func:`doubled_tables` packing). The costs
    layout keeps D innermost so a serving lookup reads one query's D
    costs as one contiguous ``[D]``-wide gather
    (:func:`lookup_tables_multi`).
    """
    r, n = fm.shape
    d = w_pads.shape[0]
    limit = n if max_len == 0 else max_len
    x = jnp.arange(n, dtype=jnp.int32)[None, :]

    slot = fm.astype(jnp.int32)
    can = slot >= 0
    slot_safe = jnp.maximum(slot, 0)
    eid = dg.out_eid[x.repeat(r, 0), slot_safe]
    nxt = dg.out_nbr[x.repeat(r, 0), slot_safe]
    succ = jnp.where(can, nxt, x)                  # self-loop when stuck
    costs = jnp.where(can[..., None], w_pads.T[eid], 0)      # [R, N, D]
    plen = jnp.where(can, 1, 0).astype(jnp.int32)

    n_sweeps = max(int(limit - 1).bit_length(), 1)

    def cond(state):
        i, _, _, _, changed = state
        return changed & (i < n_sweeps)

    def body(state):
        i, succ, costs, plen, _ = state
        packed = jnp.concatenate(
            [succ[..., None], plen[..., None], costs], axis=-1)
        gat = jnp.take_along_axis(packed, succ[..., None], axis=1)
        new_succ = gat[..., 0]
        plen = plen + gat[..., 1]
        costs = costs + gat[..., 2:]
        return i + 1, new_succ, costs, plen, jnp.any(new_succ != succ)

    changed0 = jnp.any(succ != x)
    _, succ, costs, plen, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), succ, costs, plen, changed0))

    valid = targets >= 0
    t_safe = jnp.where(valid, targets, 0).astype(jnp.int32)
    finished = (succ == t_safe[:, None]) & valid[:, None]
    plen_packed = jnp.where(finished, plen, -plen - 1).astype(plen_dtype(n))
    return costs, plen_packed


@jax.jit
def lookup_tables_multi(costs: jnp.ndarray, plen_packed: jnp.ndarray,
                        t_rows: jnp.ndarray, s: jnp.ndarray,
                        valid: jnp.ndarray | None = None):
    """Answer queries from fused multi-diff tables: one contiguous
    ``[D]``-wide gather per query plus the shared plen gather.

    Returns ``(cost [D, Q], plen [Q], finished [Q])``.
    """
    rows = t_rows.astype(jnp.int32)
    s32 = s.astype(jnp.int32)
    cost_qd = costs[rows, s32]                     # [Q, D] one gather
    pp = plen_packed[rows, s32].astype(jnp.int32)
    f = pp >= 0
    p = jnp.where(f, pp, -pp - 1)
    if valid is not None:                   # same masking contract as
        cost_qd = jnp.where(valid[:, None], cost_qd, 0)  # lookup_tables
        p = jnp.where(valid, p, 0)
        f = f & valid
    return cost_qd.T, p, f


def unpack_tables(cost, plen_packed):
    """Whole-table decode (cost, plen, finished) — for tests and direct
    table consumers; serving uses :func:`lookup_tables` per query."""
    pp = plen_packed.astype(jnp.int32)
    f = pp >= 0
    return cost, jnp.where(f, pp, -pp - 1), f


@jax.jit
def lookup_tables(cost: jnp.ndarray, plen_packed: jnp.ndarray,
                  t_rows: jnp.ndarray, s: jnp.ndarray,
                  valid: jnp.ndarray | None = None):
    """Answer queries from prepared tables: one 2-D gather each.

    Decodes the sign-packed plen: ``finished = packed >= 0``,
    ``plen = packed`` when finished else ``-packed - 1``.
    """
    rows = t_rows.astype(jnp.int32)
    s32 = s.astype(jnp.int32)
    c = cost[rows, s32]
    pp = plen_packed[rows, s32].astype(jnp.int32)
    f = pp >= 0
    p = jnp.where(f, pp, -pp - 1)
    if valid is not None:
        c = jnp.where(valid, c, 0)
        p = jnp.where(valid, p, 0)
        f = f & valid
    return c, p, f
