"""Batched ``table-search``: the query engine.

TPU-native re-expression of the reference's resident query server, which
answers each (s, t) by repeated first-move table lookups, accumulating cost
on the possibly congestion-perturbed graph (``fifo_auto --alg table-search``,
reference ``make_fifos.py:20-22``; hot loop in SURVEY.md §3.3). Instead of a
per-query C++ loop over OpenMP threads, the whole query batch advances in
lock-step: one ``lax.while_loop`` whose body gathers every active query's
next hop at once — answering an entire scenario file in one XLA call
(SURVEY.md §7 stage 4).

Semantics (must match ``models.reference.table_search_walk``):

* moves follow the **free-flow** first-move table; costs accumulate on the
  **query-time** weights (diff applied to ``w_query_pad`` only),
* a query finishes when it reaches its target; it stops unfinished on a
  ``-1`` first move (unreachable) or when the move budget (``k_moves``,
  reference ``args.py:31-36``) runs out,
* ``plen`` = number of edges followed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .device_graph import DeviceGraph


#: auto-bucketing: target lanes per bucket / bucket-count cap. ~1k lanes
#: keep the gather pipeline busy on v5e while letting each bucket's
#: while_loop exit at its own max length; 64 buckets bound the per-bucket
#: dispatch overhead (swept end-to-end on the 50k bench across rounds:
#: 64/1024 > 32/2048 > 16/4096 with the lean step — narrower buckets hug
#: the est-sorted length profile, and the per-iteration floor, not lane
#: width, is the binding cost at this size).
#:
#: Round-5 re-sweep (real chip, same 50k bench): 64/unroll=8 113 ms,
#: 32/8 117 ms, 16/8 127 ms, 64/16 117 ms, 64/4 112 ms — the current
#: default stays speed-optimal. NOTE the bench's raw gather-utilization
#: figure moves the OTHER way (16 buckets issue 4.3M lanes at 67 M/s vs
#: 64's 3.5M at 62 M/s): wider buckets pad more wasted lanes which
#: inflate the issued RATE while slowing the actual answer. The knob is
#: tuned for wall-clock, never for that ratio.
BUCKET_LANES = 1024
BUCKET_MAX = 64


def pick_buckets(q: int, n_buckets: int = 0) -> int:
    """Resolve the bucket knob: 0 = auto (≤ ``BUCKET_MAX`` buckets with ≥
    ``BUCKET_LANES`` lanes each). Either way the result is the largest
    divisor of ``q`` not exceeding the requested count, so an awkward
    batch size degrades to the nearest usable split, not to 1."""
    b = min(BUCKET_MAX, max(1, q // BUCKET_LANES)) if n_buckets == 0 \
        else min(max(1, n_buckets), max(q, 1))
    while b > 1 and q % b:
        b -= 1
    return b


def _slot_at(fm: jnp.ndarray, rows_b, x):
    """Each lane's first move: a 2-D ``(row, col)`` gather from the table
    in its resident ``[R, N]`` layout. A flat 1-D gather would need
    ``fm.reshape(-1)``, and the TPU's tiled ``[R, N]`` layout does not
    bitcast to 1-D: every call would copy the whole table (a 1.3 GB temp
    and ~3.8 ms a call at a 12,800-row serve shard on a v5e), while the
    walk's steps cost the same either way (within 0.12 ms a call)."""
    return fm[rows_b, x].astype(jnp.int32)


def _walk_buckets(step, fm, cost0_of, limit, unroll,
                  n_buckets, rows32, s32, t32, valid):
    """Shared walk scaffold for the single- and multi-diff kernels: one
    ``while_loop`` per bucket under one ``lax.scan``, lean state.

    The walk needs NO per-step arrival check: every fm row holds -1 at
    its own target (``first_move_from_dist`` construction, the
    reference's "no move at the goal"), so arriving lanes halt on the
    stuck test inside ``step`` and ``finished`` is recovered at the end
    as ``x == t``. ``halted0`` derives from the DATA (not a literal) so
    the carry stays mesh-varying under shard_map; pad lanes are halted
    at birth or a mostly-pad tail bucket would walk row 0's full path
    before its while_loop could exit.

    Both kernels read ``fm`` in place (:func:`_slot_at`).
    ``step(rows_b, x, cost, plen, halted)`` advances one move;
    ``cost0_of(x0)`` shapes the cost carry (``[Q]`` or ``[Q, D]``).
    Returns ``(cost, plen, x == t)`` flattened back to the batch axis.
    """
    def walk_bucket(rows_b, s_b, t_b, valid_b):
        x0 = jnp.where(valid_b, s_b, t_b)
        halted0 = (_slot_at(fm, rows_b, x0) < 0) | ~valid_b
        state0 = (jnp.int32(0), x0, cost0_of(x0), x0 * 0, halted0)

        def cond(state):
            i, _, _, _, halted = state
            return (~jnp.all(halted)) & (i < limit)

        def body(state):
            i, x, cost, plen, halted = state
            for _ in range(unroll):
                x, cost, plen, halted = step(rows_b, x, cost, plen,
                                             halted)
            return i + unroll, x, cost, plen, halted

        _, x, cost, plen, _ = jax.lax.while_loop(cond, body, state0)
        return cost, plen, x == t_b

    q = s32.shape[0]
    if n_buckets == 1:
        return walk_bucket(rows32, s32, t32, valid)
    qb = q // n_buckets

    def scan_body(carry, args):
        return carry, walk_bucket(*args)

    _, outs = jax.lax.scan(
        scan_body, jnp.int32(0),
        tuple(a.reshape(n_buckets, qb)
              for a in (rows32, s32, t32, valid)))
    return jax.tree.map(lambda o: o.reshape(q, *o.shape[2:]), outs)


@functools.partial(jax.jit,
                   static_argnames=("k_moves", "max_steps", "unroll",
                                    "n_buckets"))
def table_search_batch(dg: DeviceGraph, fm: jnp.ndarray,
                       t_rows: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                       w_query_pad: jnp.ndarray,
                       valid: jnp.ndarray | None = None,
                       k_moves: int = -1,
                       max_steps: int = 0, unroll: int = 8,
                       n_buckets: int = 0):
    """Answer a batch of queries against a first-move shard.

    Parameters
    ----------
    fm          : int8 [R, N] first-move rows (R = targets owned by this shard)
    t_rows      : int32 [Q] row index of each query's target within ``fm``
    s, t        : int32 [Q] global source / target node ids
    w_query_pad : int32 [M+1] query-time weights (diff applied; last = INF)
    valid       : bool [Q] padding mask (False rows return zeros, unfinished)
    k_moves     : per-batch move budget, -1 = unlimited (reference semantics)
    max_steps   : loop bound; 0 = N (safe upper bound for simple paths)
    unroll      : walk steps per while-loop iteration. Each on-device loop
                  iteration carries a fixed scheduling cost (~0.5 ms
                  measured); batching ``unroll`` gathers per iteration
                  amortizes it. Already-halted lanes re-gather harmlessly
                  (masked), so the only waste is ≤ unroll-1 trailing steps.
    n_buckets   : split the batch into equal contiguous buckets, each with
                  its OWN while_loop (one ``lax.scan`` — a single XLA
                  call). A lock-step walk runs the whole batch for
                  max-plen steps; with callers sorting queries by expected
                  length (``CPDOracle.route`` sorts by coordinate
                  distance), each bucket exits at its own max — 3.9x
                  measured on the 50k-query bench. 0 = auto
                  (:func:`pick_buckets`); 1 = single lock-step batch.
                  Results are bucket-invariant either way.

    Returns
    -------
    cost [Q] int32, plen [Q] int32, finished [Q] bool
    """
    q = s.shape[0]
    n = dg.n
    limit = n if max_steps == 0 else max_steps
    # static specialization: k_moves is a STATIC argname (its values are
    # -1 or a per-campaign constant, so recompiles are bounded), which
    # makes this a trace-time Python bool — for the common serving call
    # (-1 unlimited, the reference default, max_steps=0) the per-step
    # budget compare vanishes from the compiled program entirely (safe:
    # a CPD walk follows a simple path, so it reaches its target or a
    # -1 slot in < N moves; only an explicit truncation needs the exact
    # per-step plen cap)
    k_moves = int(k_moves)
    unlimited = k_moves < 0 and max_steps == 0
    if not unlimited:
        budget = jnp.int32(limit if k_moves < 0 else k_moves)
    if valid is None:
        valid = jnp.ones((q,), jnp.bool_)
    n_buckets = pick_buckets(q, n_buckets)

    t32 = t.astype(jnp.int32)
    rows32 = t_rows.astype(jnp.int32)

    # packed (next-node, weight) table: pair[x, k] = node x's k-th
    # out-edge as two adjacent int32s. The walk is scalar-gather-
    # throughput-bound, so gathers per step are the unit of cost; one
    # contiguous 8-byte gather replaces the separate weight and
    # next-node gathers — 3 gathers/step -> 2, measured 1.5x on the
    # bench walk. Built once per call (one [N, K] pass, trivial vs the
    # walk). The weight gather runs on the [K, N] transpose: the TPU
    # compiler takes ~30 s over an [N, K] index array at 102,400 nodes
    # and well under a second over its transpose (compile-only, PR 21).
    pair = jnp.stack([dg.out_nbr.astype(jnp.int32),
                      w_query_pad[dg.out_eid.T].T], axis=-1)

    # lean step: 2 gathers + 1 compare + 4 selects (the budget compare
    # only exists when not `unlimited`); see _walk_buckets for why no
    # per-step arrival check is needed
    def step(rows_b, x, cost, plen, halted):
        slot = _slot_at(fm, rows_b, x)
        can_move = (~halted) & (slot >= 0)
        if not unlimited:
            can_move &= plen < budget
        nxt_w = pair[x, jnp.maximum(slot, 0)]   # [Q, 2] one gather
        cost = jnp.where(can_move, cost + nxt_w[:, 1], cost)
        plen = jnp.where(can_move, plen + 1, plen)
        x = jnp.where(can_move, nxt_w[:, 0], x)
        halted = halted | ~can_move
        return x, cost, plen, halted

    cost, plen, finished = _walk_buckets(
        step, fm, lambda x0: x0 * 0, limit, unroll,
        n_buckets, rows32, s.astype(jnp.int32), t32, valid)
    finished = finished & valid
    cost = jnp.where(valid, cost, 0)
    plen = jnp.where(valid, plen, 0)
    return cost, plen, finished


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "unroll", "n_buckets"))
def table_search_multi(dg: DeviceGraph, fm: jnp.ndarray,
                       t_rows: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                       w_pads: jnp.ndarray,
                       valid: jnp.ndarray | None = None,
                       max_steps: int = 0, unroll: int = 8,
                       n_buckets: int = 0):
    """Answer a batch under D congestion diffs in ONE fused walk.

    The reference campaign serves one round per diff file, re-walking
    every query each round (reference ``process_query.py:178``). But a
    table-search trajectory is **diff-independent** — moves follow the
    free-flow first-move table; only cost accumulation sees the
    query-time weights (reference semantics, this module's header). So
    one walk can accumulate all D diffs' costs at once: per step, one
    packed (next-node, edge-id) gather drives the move and one ``[D]``
    row gather from the transposed weight matrix accumulates every
    diff's cost — ~3 gathers/step total instead of 2 PER DIFF for D
    sequential rounds (≈ 2D/3 fewer gathers, bounded by the D-wide
    row-gather's bandwidth).

    Parameters as :func:`table_search_batch` except ``w_pads``: int32
    ``[D, M+1]`` — one padded weight row per diff (row d =
    ``graph.padded_weights(w_diff_d)``; include free flow as a row to
    get it fused too). There is no ``k_moves``: the fused path serves
    the unlimited reference default; budgeted campaigns fall back to
    sequential rounds (``cli.process_query``). ``max_steps`` truncates
    exactly like the single-diff kernel's.

    Returns ``(cost [D, Q], plen [Q], finished [Q])`` — plen/finished
    are shared across diffs because the trajectory is.
    """
    q = s.shape[0]
    n = dg.n
    limit = n if max_steps == 0 else max_steps
    if valid is None:
        valid = jnp.ones((q,), jnp.bool_)
    n_buckets = pick_buckets(q, n_buckets)
    d = w_pads.shape[0]

    t32 = t.astype(jnp.int32)
    rows32 = t_rows.astype(jnp.int32)

    # packed (next-node, edge-id) pair + [M+1, D] transposed weights:
    # the per-step [Q, D] weight gather reads D contiguous int32s per
    # lane, the same widening trick as the single-diff (next, w) pair
    pair = jnp.stack([dg.out_nbr.astype(jnp.int32),
                      dg.out_eid.astype(jnp.int32)], axis=-1)
    w_t = w_pads.T                                   # [M+1, D]

    # mirror table_search_batch's truncation contract: an explicit
    # max_steps caps plen EXACTLY per step (the while cond alone would
    # overshoot by up to unroll-1 moves)
    bounded = max_steps != 0

    def step(rows_b, x, cost, plen, halted):
        slot = _slot_at(fm, rows_b, x)
        can_move = (~halted) & (slot >= 0)
        if bounded:
            can_move &= plen < limit
        nxt_eid = pair[x, jnp.maximum(slot, 0)]  # [Q, 2]
        w_row = w_t[nxt_eid[:, 1]]               # [Q, D] one gather
        cost = jnp.where(can_move[:, None], cost + w_row, cost)
        plen = jnp.where(can_move, plen + 1, plen)
        x = jnp.where(can_move, nxt_eid[:, 0], x)
        halted = halted | ~can_move
        return x, cost, plen, halted

    def cost0_of(x0):
        return (jnp.zeros((x0.shape[0], d), jnp.int32)
                + (x0 * 0)[:, None])

    cost, plen, finished = _walk_buckets(
        step, fm, cost0_of, limit, unroll,
        n_buckets, rows32, s.astype(jnp.int32), t32, valid)
    finished = finished & valid
    cost = jnp.where(valid[:, None], cost, 0).T      # [D, Q]
    plen = jnp.where(valid, plen, 0)
    return cost, plen, finished


@functools.partial(jax.jit, static_argnames=("k",))
def extract_paths(dg: DeviceGraph, fm: jnp.ndarray, t_rows: jnp.ndarray,
                  s: jnp.ndarray, t: jnp.ndarray, k: int):
    """Materialize the first ``k`` moves of each query's CPD path.

    The reference's prefix extraction (``--k-moves``, reference
    ``args.py:31-36``: "number of moves to extract"): beyond a cost, a
    navigation client wants the next few road segments. One ``lax.scan``
    over ``k`` steps collects the node sequence for the whole batch at
    once.

    Returns ``(nodes, plen)``: int32 ``[Q, k+1]`` node ids — row q starts
    at ``s[q]``; after the path ends (target reached or stuck) the last
    node repeats — and the number of real moves taken (≤ k).
    """
    rows32 = t_rows.astype(jnp.int32)
    t32 = t.astype(jnp.int32)
    x0 = s.astype(jnp.int32)

    def step(x, _):
        slot = fm[rows32, x].astype(jnp.int32)
        can = (slot >= 0) & (x != t32)
        nxt = dg.out_nbr[x, jnp.maximum(slot, 0)]
        x = jnp.where(can, nxt, x)
        return x, (x, can)

    _, (xs, cans) = jax.lax.scan(step, x0, None, length=k)
    nodes = jnp.concatenate([x0[None, :], xs], axis=0).T  # [Q, k+1]
    plen = cans.sum(axis=0).astype(jnp.int32)
    return nodes, plen
