"""Sharded CPD build and sharded query execution.

The two distributed phases of the system, on a device mesh:

* **Build** (reference: per-worker ``make_cpd_auto`` processes launched over
  ssh/tmux, SURVEY.md §3.1): every mesh shard computes first-move rows for
  the targets it owns, in parallel, with zero cross-shard traffic — the
  batch axis of the min-plus iteration is sharded over ``worker``, the graph
  is replicated, and GSPMD keeps each row's computation on its row's device.
  The only collective is the all-reduce of the convergence flag inside the
  Bellman-Ford ``while_loop``.

* **Query** (reference: per-worker FIFO round-trips driven by a head-node
  thread pool, SURVEY.md §3.3): queries arrive pre-routed ``[D, W, Q]`` (row
  w = queries whose target w owns, the invariant of
  ``process_query.py:56-57``), an optional leading data axis splits the
  batch, and each shard walks its own queries against its own fm rows via
  ``shard_map`` — explicitly no resharding of the fm table.

Compiled programs are cached at module level, keyed on (mesh, static
shape knobs): a resident server calls these thousands of times, and an
eagerly re-traced shard_map would pay a device round-trip per while_loop
iteration.

Padding convention: rectangular arrays everywhere; targets pad with -1,
queries pad with ``valid=False`` rows. Padding is computed-but-masked, the
usual SPMD trade.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import DeviceGraph, table_search_batch
from .mesh import WORKER_AXIS, DATA_AXIS, LANE_AXIS, replicated


def pad_targets(controller, dtype=np.int32) -> np.ndarray:
    """[W, R] owned targets per worker, -1-padded to the max shard size."""
    w = controller.maxworker
    r = max(controller.max_owned, 1)
    out = np.full((w, r), -1, dtype)
    for wid in range(w):
        owned = controller.owned(wid)
        out[wid, :len(owned)] = owned
    return out


# --------------------------------------------------------------------- build

@functools.lru_cache(maxsize=None)
def _build_fn(mesh: Mesh, n_workers: int, max_iters: int,
              with_dists: bool, kind: str = "ell",
              kernel_sig: tuple | None = None,
              axis: str = WORKER_AXIS):
    """One compiled sharded builder for all three relaxation kernels.

    ``kind`` selects the distance stage: ``"sweep"`` (fast-sweeping grid
    scans, sig ``(h, w, shifts, n_left)``), ``"shift"`` (gather-free shift
    relaxation, sig ``(shifts, n, k_left)``), ``"frontier"``
    (delta-stepping queue, sig ``(n, f, delta, s_unroll)``),
    ``"ellsplit"`` or ``"ell"`` (padded-ELL gather, no sig). Extra kernel
    operands arrive replicated. Everything else — shardings, target
    layout, first-move extraction, with_dists outputs — is shared, so
    the paths cannot drift.

    Runs under ``shard_map`` so each shard's relaxation ``while_loop``
    converges on its OWN flag — no per-sweep all-reduce, no
    slowest-shard coupling (a GSPMD-jit build had a single global loop:
    every shard swept until the last one converged, which is why the
    round-2 weak-scaling bench REGRESSED with worker count).
    """
    from ..ops.bellman_ford import dist_to_targets, first_move_from_dist
    from ..ops.ell_split import _ellsplit_dist_fn
    from ..ops.frontier_relax import _frontier_dist_fn
    from ..ops.grid_sweep import _sweep_dist_fn
    from ..ops.shift_relax import _dist_fn

    frontier = False
    if kind == "sweep":
        n_kernel_ops = 8
        kernel_dist = _sweep_dist_fn(*kernel_sig, max_iters)
    elif kind == "shift":
        n_kernel_ops = 3
        kernel_dist = _dist_fn(*kernel_sig, max_iters)
    elif kind == "ellsplit":
        n_kernel_ops = 5
        kernel_dist = _ellsplit_dist_fn(*kernel_sig, max_iters)
    elif kind == "frontier":
        # frontier consumes the DeviceGraph arrays too (sig carries the
        # queue knobs); only in_nbr is an extra operand
        n_kernel_ops = 1
        frontier = True
        kernel_dist = _frontier_dist_fn(*kernel_sig, max_iters)
    else:
        n_kernel_ops = 0
        kernel_dist = None

    def _local(dg, *ops_and_tgt):
        # local blocks: tgt [B, 1] (this shard's column); graph + kernel
        # operands replicated
        *kernel_ops, tgt_b1 = ops_and_tgt
        tgts = tgt_b1.reshape(-1)
        if frontier:
            dist = kernel_dist(dg.out_nbr, dg.out_eid, dg.w_pad,
                               *kernel_ops, tgts)
        elif kernel_dist is not None:
            dist = kernel_dist(*kernel_ops, tgts)
        else:
            dist = dist_to_targets(dg, tgts, max_iters=max_iters)
        fm = first_move_from_dist(dg, tgts, dist)
        if with_dists:
            return fm[None], dist[None]
        return fm[None]

    out_spec = P(axis, None, None)
    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), *([P()] * n_kernel_ops), P(None, axis)),
        out_specs=(out_spec, out_spec) if with_dists else out_spec,
    )
    return jax.jit(sm)


def build_fm_sharded(dg: DeviceGraph, targets_wr: np.ndarray,
                     mesh: Mesh, chunk: int = 0,
                     max_iters: int = 0, with_dists: bool = False,
                     kernel=None, axis: str = WORKER_AXIS):
    """Build the full sharded CPD: int8 [W, R, N], axis 0 on ``worker``.

    ``chunk`` bounds per-device live distance rows (0 = whole shard at
    once): the host loops over column-chunks of ``targets_wr`` so each
    device only ever materializes ``[chunk, N]`` int32 distances, then
    concatenates the int8 results — the memory staging the reference gets
    from per-block CPD files (``README.md:92``).

    ``with_dists=True`` additionally returns the converged distance table
    int32 [W, R, N] (4x the fm memory): free-flow queries then need no
    walk at all — one gather answers d(s→t) (SURVEY.md §5: "distance-only
    answers need no extraction").

    ``kernel``: optional ``(kind, structure)`` from
    ``models.cpd.pick_build_kernel`` — selects the fast-sweeping /
    shift / ELL distance stage (default ELL).

    ``axis``: the mesh axis the target rows shard over — the campaign
    mesh's ``worker`` axis by default, or a worker-local mesh's
    ``lane`` axis (:func:`build_fm_lanes`): the per-target computation
    is axis-agnostic, only the sharding spec names change.
    """
    w, r = targets_wr.shape
    if mesh.shape[axis] != w:
        raise ValueError(
            f"targets rows ({w}) != mesh {axis} axis "
            f"({mesh.shape[axis]})")
    kind, st = kernel if kernel is not None else ("ell", None)
    if kind == "sweep":
        fn = _build_fn(mesh, w, max_iters, with_dists, kind="sweep",
                       kernel_sig=(st.height, st.width, st.shifts,
                                   st.n_left), axis=axis)
        build = lambda dg_, t_: fn(  # noqa: E731
            dg_, st.wl, st.wr, st.wd, st.wu, st.w_shift, st.src_left,
            st.dst_left, st.w_left, t_)
    elif kind == "shift":
        fn = _build_fn(mesh, w, max_iters, with_dists, kind="shift",
                       kernel_sig=(st.shifts, st.n, st.k_left),
                       axis=axis)
        build = lambda dg_, t_: fn(  # noqa: E731
            dg_, st.w_shift, st.nbr_left, st.w_left, t_)
    elif kind == "ellsplit":
        fn = _build_fn(mesh, w, max_iters, with_dists, kind="ellsplit",
                       kernel_sig=(st.n, st.k0, len(st.u_ov)),
                       axis=axis)
        build = lambda dg_, t_: fn(  # noqa: E731
            dg_, st.nbr0, st.w0, st.u_ov, st.v_ov, st.w_ov, t_)
    elif kind == "frontier":
        fn = _build_fn(mesh, w, max_iters, with_dists, kind="frontier",
                       kernel_sig=(st.n, st.f, st.delta, st.s_unroll),
                       axis=axis)
        build = lambda dg_, t_: fn(dg_, st.in_nbr, t_)  # noqa: E731
    else:
        build = _build_fn(mesh, w, max_iters, with_dists, axis=axis)
    if chunk <= 0 or chunk >= r:
        chunks = [targets_wr]
    else:
        # equal chunk sizes (pad the target list) so every chunk hits the
        # same compiled program
        pad = (-r) % chunk
        if pad:
            targets_wr = np.concatenate(
                [targets_wr, np.full((w, pad), -1, targets_wr.dtype)], axis=1)
        chunks = [targets_wr[:, i:i + chunk]
                  for i in range(0, targets_wr.shape[1], chunk)]
    parts = [build(dg, jnp.asarray(c.T)) for c in chunks]
    if with_dists:
        fms, dists = zip(*parts)
        fm = fms[0] if len(fms) == 1 else jnp.concatenate(fms, axis=1)
        dist = (dists[0] if len(dists) == 1
                else jnp.concatenate(dists, axis=1))
        return fm[:, :r], dist[:, :r]
    fm = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return fm[:, :r]


# ------------------------------------------------------- worker lanes
#
# Worker-LOCAL multi-device execution (the ``lane`` axis,
# ``parallel.mesh.make_worker_mesh``): one worker process drives several
# devices, splitting its own batches/build chunks across them. Unlike
# the campaign mesh above, nothing here crosses shards — the fm rows
# are this ONE shard's and replicate over lanes; only the query/target
# axis splits. Every lane function is bit-identical to its
# single-device twin (per-query/per-target computations are
# independent; tests/test_mesh.py pins 1/2/4/8 lanes).

def build_fm_lanes(dg: DeviceGraph, pad: np.ndarray, mesh: Mesh,
                   kind: str, structure, max_iters: int = 0):
    """One build chunk's target pad (int32 ``[C]``, -1-padded) computed
    across the worker's lanes: lane l builds the contiguous rows
    ``pad[l*C/L:(l+1)*C/L]``. Returns the async device fm block
    ``[C, N]`` in original target order — the same contract as the
    single-device chunk compute, so the pipelined build's stager/flush
    machinery is unchanged. ``C`` must divide by the lane count
    (callers gate; pads are fixed pow2-friendly shapes)."""
    lanes = mesh.shape[LANE_AXIS]
    c = int(np.asarray(pad).shape[0])
    targets_lr = np.asarray(pad, np.int32).reshape(lanes, c // lanes)
    fm = build_fm_sharded(dg, targets_lr, mesh, chunk=0,
                          max_iters=max_iters,
                          kernel=(kind, structure), axis=LANE_AXIS)
    return fm.reshape(c, -1)


@functools.lru_cache(maxsize=None)
def _lane_walk_fn(mesh: Mesh, max_steps: int, k_moves: int,
                  kernel: str):
    """One compiled lane-split walk: queries ``[L, Qb]`` sharded over
    ``lane``, the shard's fm replicated. ``kernel`` joins the cache key
    exactly like ``_query_fn``'s — each lane runs its bucket subset
    through the Pallas or XLA walk unchanged."""
    q2 = P(LANE_AXIS, None)

    def _local(dg, fm, rows, s, t, valid, w_pad):
        shape = s.shape
        if kernel == "pallas":
            from ..ops.pallas_walk import pallas_walk_batch as walk
        else:
            walk = table_search_batch
        cost, plen, fin = walk(
            dg, fm, rows.reshape(-1), s.reshape(-1), t.reshape(-1),
            w_pad, valid=valid.reshape(-1), k_moves=k_moves,
            max_steps=max_steps)
        return (cost.reshape(shape), plen.reshape(shape),
                fin.reshape(shape))

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(), q2, q2, q2, q2, P()),
        out_specs=(q2, q2, q2),
        # JAX 0.9's Pallas interpreter (pallas/hlo_interpreter.py) mixes
        # the mesh-varying operands with its own unvarying grid indices
        # and loop carries, which check_vma rejects ("Scan carry input
        # and output got mismatched varying manual axes ... as a
        # temporary workaround pass check_vma=False"); the kernel's own
        # out_shape carries the vma
        check_vma=kernel != "pallas",
    )
    return jax.jit(sm)


def lane_walk_program(dg: DeviceGraph, fm, t_rows, s, t, valid, w_pad,
                      mesh: Mesh, k_moves: int = -1,
                      max_steps: int = 0, kernel: str = "xla"):
    """``(jitted_fn, operands)`` of one lane-split walk call — the same
    cached jit :func:`walk_lanes` dispatches, with the flat ``[Q]``
    query arrays reshaped to ``[L, Q/L]`` and lane-sharded exactly as
    it ships them. Split out so the engine's AOT cost capture lowers
    the program the mesh path ACTUALLY ran (an XLA cache hit), instead
    of going dark under lanes."""
    lanes = mesh.shape[LANE_AXIS]
    q = int(np.asarray(s).shape[0])
    qs = NamedSharding(mesh, P(LANE_AXIS, None))
    packed = tuple(np.asarray(a).reshape(lanes, q // lanes)
                   for a in (t_rows, s, t, valid))
    # ONE device_put for the whole pack (same rationale as
    # query_sharded: each separate transfer pays a fixed round trip)
    args = jax.device_put(packed, qs)
    fn = _lane_walk_fn(mesh, max_steps, int(k_moves), str(kernel))
    return fn, (dg, fm, *args, w_pad)


def walk_lanes(dg: DeviceGraph, fm, t_rows, s, t, valid, w_pad,
               mesh: Mesh, k_moves: int = -1, max_steps: int = 0,
               kernel: str = "xla"):
    """Split one worker's walk batch across its lanes.

    Flat ``[Q]`` inputs (the engine's est-sorted, pow2-padded batch);
    ``Q`` must divide by the lane count (the engine gates). Lane l
    walks the contiguous slice ``[l*Q/L, (l+1)*Q/L)`` — contiguous in
    the sorted order, so each lane's auto-bucketing
    (``pick_buckets``) sees the same monotone length profile the
    single-device kernel does, and results are bucket-invariant
    (pinned), hence bit-identical after the flat reshape back.
    Returns ``(cost, plen, finished)`` flat ``[Q]`` device arrays."""
    q = int(np.asarray(s).shape[0])
    fn, ops = lane_walk_program(dg, fm, t_rows, s, t, valid, w_pad,
                                mesh, k_moves=k_moves,
                                max_steps=max_steps, kernel=kernel)
    cost, plen, fin = fn(*ops)
    return cost.reshape(q), plen.reshape(q), fin.reshape(q)


@functools.lru_cache(maxsize=None)
def _mat_fn(mesh: Mesh, k_out: int, max_steps: int):
    """One-to-many ETA row with the JOIN ON MESH: each shard walks its
    routed slice, scatters its answers into a dense ``[k_out]`` row at
    the slot positions the router assigned, and a ``psum`` over both
    mesh axes assembles the complete row as a collective — no head-side
    fan-out/join, no per-target result plumbing."""
    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    def _local(dg, fm_local, rows, s, t, valid, slots, w_pad):
        v = valid.reshape(-1)
        cost, _plen, fin = table_search_batch(
            dg, fm_local[0], rows.reshape(-1), s.reshape(-1),
            t.reshape(-1), w_pad, valid=v, k_moves=-1,
            max_steps=max_steps)
        # scatter-add into [k_out + 1]: pad slots dump into the extra
        # slot; every real target index lives in exactly ONE (d, w, q)
        # slot fleet-wide, so the psum is a disjoint union, not a sum
        idx = jnp.where(v, slots.reshape(-1), k_out)
        row_c = jnp.zeros(k_out + 1, jnp.int32).at[idx].add(
            jnp.where(v, cost, 0))
        row_f = jnp.zeros(k_out + 1, jnp.int32).at[idx].add(
            fin.astype(jnp.int32))
        row_c = jax.lax.psum(row_c, (DATA_AXIS, WORKER_AXIS))
        row_f = jax.lax.psum(row_f, (DATA_AXIS, WORKER_AXIS))
        return row_c[:k_out], row_f[:k_out] > 0

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), q3, q3, q3, q3, q3,
                  P()),
        out_specs=(P(), P()),
    )
    return jax.jit(sm)


def query_mat_sharded(dg: DeviceGraph, fm_wrn, t_rows, s, t, valid,
                      slots, w_pad, mesh: Mesh, k_out: int,
                      max_steps: int = 0):
    """Answer one ``mat`` family row (one source, ``k_out`` targets)
    with on-mesh collectives: routed ``[D, W, Q]`` inputs as in
    :func:`query_sharded` plus ``slots`` (each routed slot's position
    in the output row, -1 on padding). Returns ``(cost [k_out] int32,
    finished [k_out] bool)`` — already in target order, replicated, so
    the host reads one device and does no join at all."""
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    args = jax.device_put((t_rows, s, t, valid, slots), qs)
    fn = _mat_fn(mesh, int(k_out), max_steps)
    return fn(dg, fm_wrn, *args, jnp.asarray(w_pad))


# ----------------------------------------------------------- cost tables

@functools.lru_cache(maxsize=None)
def _tables_fn(mesh: Mesh, max_len: int):
    from ..ops.pointer_doubling import doubled_tables

    def _local(dg, fm_local, tgt_local, w_pad):
        # local blocks: fm [1, R, N], tgt [1, R]
        return doubled_tables(dg, fm_local[0], tgt_local[0], w_pad,
                              max_len=max_len)

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), P(WORKER_AXIS, None),
                  P()),
        out_specs=(P(WORKER_AXIS, None), P(WORKER_AXIS, None)),
    )

    def _wrap(dg, fm_wrn, tgt_wr, w_pad):
        c, p = sm(dg, fm_wrn, tgt_wr, w_pad)
        # shard_map emits [W*R, N] (axis-0 concat of local [R, N]); restore
        # the worker axis
        w = fm_wrn.shape[0]
        return c.reshape(w, -1, dg.n), p.reshape(w, -1, dg.n)

    return jax.jit(_wrap)


def build_tables_sharded(dg: DeviceGraph, fm_wrn: jax.Array,
                         targets_wr: np.ndarray, w_query_pad, mesh: Mesh,
                         max_len: int = 0):
    """Pointer-doubling cost/plen/finished tables, one shard per worker
    (each worker doubles only its own rows — zero cross-shard traffic)."""
    tgt = jax.device_put(
        jnp.asarray(targets_wr, jnp.int32),
        NamedSharding(mesh, P(WORKER_AXIS, None)))
    fn = _tables_fn(mesh, max_len)
    return fn(dg, fm_wrn, tgt, jnp.asarray(w_query_pad))


@functools.lru_cache(maxsize=None)
def _tables_multi_fn(mesh: Mesh, max_len: int):
    from ..ops.pointer_doubling import doubled_tables_multi

    def _local(dg, fm_local, tgt_local, w_pads):
        # local blocks: fm [1, R, N], tgt [1, R]; w_pads replicated
        return doubled_tables_multi(dg, fm_local[0], tgt_local[0],
                                    w_pads, max_len=max_len)

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), P(WORKER_AXIS, None),
                  P()),
        out_specs=(P(WORKER_AXIS, None, None), P(WORKER_AXIS, None)),
    )

    def _wrap(dg, fm_wrn, tgt_wr, w_pads):
        c, p = sm(dg, fm_wrn, tgt_wr, w_pads)
        # shard_map emits [W*R, N, D] / [W*R, N]; restore the worker axis
        w = fm_wrn.shape[0]
        return (c.reshape(w, -1, dg.n, c.shape[-1]),
                p.reshape(w, -1, dg.n))

    return jax.jit(_wrap)


def build_tables_multi_sharded(dg: DeviceGraph, fm_wrn: jax.Array,
                               targets_wr: np.ndarray, w_pads,
                               mesh: Mesh, max_len: int = 0):
    """Fused multi-diff pointer-doubling tables, one shard per worker.

    ``w_pads`` int32 [D, M+1]. Returns ``(costs [W, R, N, D],
    plen_packed [W, R, N])`` — D diffs' tables for ~one prepare's
    gather traffic (``ops.pointer_doubling.doubled_tables_multi``).
    """
    tgt = jax.device_put(
        jnp.asarray(targets_wr, jnp.int32),
        NamedSharding(mesh, P(WORKER_AXIS, None)))
    fn = _tables_multi_fn(mesh, max_len)
    return fn(dg, fm_wrn, tgt, jnp.asarray(w_pads, jnp.int32))


@functools.lru_cache(maxsize=None)
def _query_table_multi_fn(mesh: Mesh, d: int):
    from ..ops.pointer_doubling import lookup_tables_multi

    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    def _local(costs, plen_packed, rows, s, valid):
        shape = s.shape
        c, p, f = lookup_tables_multi(costs[0], plen_packed[0],
                                      rows.reshape(-1), s.reshape(-1),
                                      valid.reshape(-1))
        return (c.reshape(d, *shape), p.reshape(shape), f.reshape(shape))

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(WORKER_AXIS, None, None, None),
                  P(WORKER_AXIS, None, None), q3, q3, q3),
        out_specs=(P(None, DATA_AXIS, WORKER_AXIS, None), q3, q3))
    return jax.jit(sm)


def query_tables_multi_sharded(tables, t_rows, s, valid, mesh: Mesh):
    """Answer routed [Dg, W, Q] queries from fused multi-diff tables."""
    costs, plen_packed = tables
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    rows_d, s_d, v_d = jax.device_put((t_rows, s, valid), qs)
    fn = _query_table_multi_fn(mesh, int(costs.shape[-1]))
    return fn(costs, plen_packed, rows_d, s_d, v_d)


@functools.lru_cache(maxsize=None)
def _query_table_fn(mesh: Mesh):
    from ..ops.pointer_doubling import lookup_tables

    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    def _local(cost, plen_packed, rows, s, valid):
        shape = s.shape
        c, p, f = lookup_tables(cost[0], plen_packed[0],
                                rows.reshape(-1), s.reshape(-1),
                                valid.reshape(-1))
        return c.reshape(shape), p.reshape(shape), f.reshape(shape)

    t3 = P(WORKER_AXIS, None, None)
    sm = jax.shard_map(_local, mesh=mesh,
                       in_specs=(t3, t3, q3, q3, q3),
                       out_specs=(q3, q3, q3))
    return jax.jit(sm)


def query_tables_sharded(tables, t_rows, s, valid, mesh: Mesh):
    """Answer routed [D, W, Q] queries from prepared cost tables."""
    cost, plen_packed = tables
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    rows_d, s_d, v_d = jax.device_put((t_rows, s, valid), qs)
    return _query_table_fn(mesh)(cost, plen_packed, rows_d, s_d, v_d)


# --------------------------------------------------------------------- paths

@functools.lru_cache(maxsize=None)
def _paths_fn(mesh: Mesh, k: int):
    from ..ops.table_search import extract_paths

    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    def _local(dg, fm_local, rows, s, t):
        shape = s.shape
        nodes, plen = extract_paths(dg, fm_local[0], rows.reshape(-1),
                                    s.reshape(-1), t.reshape(-1), k=k)
        return (nodes.reshape(*shape, k + 1), plen.reshape(shape))

    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), q3, q3, q3),
        out_specs=(P(DATA_AXIS, WORKER_AXIS, None, None), q3),
    )
    return jax.jit(sm)


def query_paths_sharded(dg: DeviceGraph, fm_wrn: jax.Array,
                        t_rows: np.ndarray, s: np.ndarray, t: np.ndarray,
                        mesh: Mesh, k: int):
    """Materialize k-move path prefixes for routed [D, W, Q] queries.

    Returns ``(nodes [D, W, Q, k+1], moves [D, W, Q])`` — each shard scans
    only its own queries against its own fm rows (the reference's
    ``--k-moves`` extraction, reference ``args.py:31-36``, batched).
    """
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    args = jax.device_put((t_rows, s, t), qs)
    return _paths_fn(mesh, k)(dg, fm_wrn, *args)


# --------------------------------------------------------------------- query

@functools.lru_cache(maxsize=None)
def _query_dist_fn(mesh: Mesh):
    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    def _local(dist_local, rows, s):
        # dist_local [1, R, N]; rows/s [D/|data|, 1, Q]
        shape = s.shape
        cost = dist_local[0][rows.reshape(-1), s.reshape(-1)]
        return cost.reshape(shape)

    sm = jax.shard_map(_local, mesh=mesh,
                       in_specs=(P(WORKER_AXIS, None, None), q3, q3),
                       out_specs=q3)
    return jax.jit(sm)


def query_dist_sharded(dist_wrn: jax.Array, t_rows: np.ndarray,
                       s: np.ndarray, mesh: Mesh) -> jax.Array:
    """Free-flow fast path: d(s → t) by one sharded gather, no walk.

    Inputs ``[D, W, Q]`` as in :func:`query_sharded`; returns cost
    ``[D, W, Q]`` (INF where unreachable).
    """
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    rows_d, s_d = jax.device_put((t_rows, s), qs)
    return _query_dist_fn(mesh)(dist_wrn, rows_d, s_d)


@functools.lru_cache(maxsize=None)
def _query_fn(mesh: Mesh, max_steps: int, k_moves: int = -1,
              kernel: str = "xla"):
    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    # the function's name is the compiled program's (``jit_mesh_walk``):
    # device traces find the campaign walk by it
    def mesh_walk(dg, fm_local, rows, s, t, valid, w_pad):
        # local blocks: fm [1, R, N]; queries [D/|data|, 1, Q].
        # k_moves is part of THIS function's cache key (a per-campaign
        # constant), so the kernel sees a Python int and its static
        # no-budget specialization applies — a traced k_moves operand
        # would force the per-step budget compare back in. `kernel`
        # joins the key the same way: "pallas" swaps in the fused walk
        # (ops.pallas_walk, bit-identical answers) per shard
        fm2 = fm_local[0]
        shape = s.shape
        if kernel == "pallas":
            from ..ops.pallas_walk import pallas_walk_batch as walk
        else:
            walk = table_search_batch
        cost, plen, fin = walk(
            dg, fm2, rows.reshape(-1), s.reshape(-1), t.reshape(-1), w_pad,
            valid=valid.reshape(-1), k_moves=k_moves, max_steps=max_steps)
        return (cost.reshape(shape), plen.reshape(shape), fin.reshape(shape))

    sm = jax.shard_map(
        mesh_walk, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), q3, q3, q3, q3, P()),
        out_specs=(q3, q3, q3),
        # JAX 0.9's Pallas interpreter (pallas/hlo_interpreter.py) mixes
        # the mesh-varying operands with its own unvarying grid indices
        # and loop carries, which check_vma rejects ("Scan carry input
        # and output got mismatched varying manual axes ... as a
        # temporary workaround pass check_vma=False"); the kernel's own
        # out_shape carries the vma
        check_vma=kernel != "pallas",
    )
    return jax.jit(sm)


@functools.lru_cache(maxsize=None)
def _query_multi_fn(mesh: Mesh, max_steps: int, d: int):
    from ..ops.table_search import table_search_multi

    q3 = P(DATA_AXIS, WORKER_AXIS, None)

    # compiled as ``jit_mesh_walk_multi`` (see _query_fn)
    def mesh_walk_multi(dg, fm_local, rows, s, t, valid, w_pads):
        fm2 = fm_local[0]
        shape = s.shape
        cost, plen, fin = table_search_multi(
            dg, fm2, rows.reshape(-1), s.reshape(-1), t.reshape(-1),
            w_pads, valid=valid.reshape(-1), max_steps=max_steps)
        return (cost.reshape(d, *shape), plen.reshape(shape),
                fin.reshape(shape))

    sm = jax.shard_map(
        mesh_walk_multi, mesh=mesh,
        in_specs=(P(), P(WORKER_AXIS, None, None), q3, q3, q3, q3, P()),
        out_specs=(P(None, DATA_AXIS, WORKER_AXIS, None), q3, q3),
    )
    return jax.jit(sm)


def query_multi_sharded(dg: DeviceGraph, fm_wrn: jax.Array,
                        t_rows: np.ndarray, s: np.ndarray, t: np.ndarray,
                        valid: np.ndarray, w_pads, mesh: Mesh,
                        max_steps: int = 0):
    """Fused multi-diff campaign on the mesh: one walk, D cost sets.

    ``w_pads`` int32 [D, M+1] (one padded weight row per diff). Returns
    ``(cost [D, Dg, W, Q], plen [Dg, W, Q], finished [Dg, W, Q])`` for
    routed ``[Dg, W, Q]`` batches — trajectories are diff-independent,
    so plen/finished are shared (``ops.table_search.table_search_multi``).
    """
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    args = jax.device_put((t_rows, s, t, valid), qs)
    w = jnp.asarray(w_pads, jnp.int32)
    fn = _query_multi_fn(mesh, max_steps, int(w.shape[0]))
    return fn(dg, fm_wrn, *args, w)


def query_sharded(dg: DeviceGraph, fm_wrn: jax.Array,
                  t_rows: np.ndarray, s: np.ndarray, t: np.ndarray,
                  valid: np.ndarray, w_query_pad, mesh: Mesh,
                  k_moves: int = -1, max_steps: int = 0,
                  kernel: str = "xla"):
    """Answer routed query batches on the mesh.

    Inputs are ``[D, W, Q]`` (data axis × worker axis × padded queries):
    ``t_rows`` = local fm row of each query's target, ``valid`` masks
    padding. Returns ``(cost, plen, finished)`` each ``[D, W, Q]``.
    ``kernel``: ``"xla"`` (the reference walk) or ``"pallas"`` (the
    fused kernel, ``ops.pallas_walk``) — callers resolve the
    ``DOS_WALK_KERNEL`` knob, this layer just compiles what it is told.
    """
    qs = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    # ONE device_put for the whole query pack: each separate transfer
    # costs a fixed round trip; and never jnp.asarray first — that is a
    # second, default-device transfer before the resharding copy
    args = jax.device_put((t_rows, s, t, valid), qs)
    fn = _query_fn(mesh, max_steps, int(k_moves), str(kernel))
    return fn(dg, fm_wrn, *args, jnp.asarray(w_query_pad))
