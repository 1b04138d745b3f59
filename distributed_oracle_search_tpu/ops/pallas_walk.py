"""Pallas-fused ``table-search`` walk kernel (ROADMAP item 1).

The XLA walk (:func:`.table_search.table_search_batch`) is scalar-
gather-throughput bound: every step issues generic XLA gathers (fm slot
+ packed (next, weight) pair) that round-trip HBM, and the bench pins
per-query TPU throughput at 0.71x one CPU core while the bulk dist
path — one gather per query — runs 2.5x. This module re-expresses the
same walk as ONE Pallas kernel so the per-step state never leaves the
chip:

* **grid = the bucket split.** ``pick_buckets`` (the ``BUCKET_LANES`` /
  ``BUCKET_MAX`` auto-bucketing the XLA kernel scans over) becomes the
  kernel grid: one program per bucket, each walking its own
  ``while_loop`` to its own max length. TPU grid programs run
  sequentially on a core, so scratch persists across buckets — which is
  what makes the double buffer below work.
* **double-buffered first-move row tiles.** Each bucket's queries need
  ``qb`` first-move rows (``fm[t_rows[q]]``, one row per lane, fixed
  for the whole walk). The row ids arrive via scalar prefetch
  (``PrefetchScalarGridSpec``), and the loader DMAs bucket ``i+1``'s
  rows into the spare VMEM tile slot while bucket ``i`` walks — the
  next bucket's first gather never waits on HBM. Under interpret mode
  (the CPU tier-1 path) TPU DMA semaphores don't exist, so the loader
  degrades to a direct ref copy with identical semantics.
* **fused diff application.** Costs accumulate on the QUERY-TIME
  weights inside the same loop (``w_query_pad[out_eid[x, slot]]``) —
  free-flow moves, diffed costs, exactly the module-header contract of
  ``ops.table_search``.

**The row-tile loader is a seam — now occupied.** ``_stage_row_direct``
/ ``_stage_row_dma`` materialize one fm row into one tile lane, and the
compressed-CPD tier (ROADMAP item 1 after the PR 10 re-anchor;
``models.resident``) plugs in here: under ``packed4=True`` the loaders
stage the PACK4 nibble row — half the HBM traffic — and the walk
widens it with an on-chip nibble unpack, so raw rows only ever exist
in VMEM. RLE-resident shards decompress per batch through the XLA
run-start search (``CompressedFM.decompress_rows``) before either
kernel runs; the walk loop itself never changes.

Kernel selection (``DOS_WALK_KERNEL``, via ``utils.env``):

=========  ==========================================================
``auto``   the XLA walk, on every backend (default)
``pallas`` the fused kernel: interpret mode on non-TPU hosts (the
           parity/testing path); refused with the reason on a TPU
           (the compiler rejects it) and wherever the working set is
           over the VMEM budget
``xla``    the XLA walk (the reference implementation)
=========  ==========================================================

``auto`` does not pick this kernel on a TPU: the chip's compiler
refuses it (Mosaic cannot lower its in-VMEM gathers, and its ``(1, qb)``
bucket blocks break the (8, 128) tiling rule), and its working set
is over the VMEM budget at any graph above a few thousand nodes
(``tests/test_chip_compile.py``). An explicit ``pallas`` request that
cannot run raises :class:`WalkKernelUnavailable`; it never serves the
XLA walk under the Pallas name.

Semantics are exactly :func:`.table_search.table_search_batch`'s
(itself pinned to ``models.reference.table_search_walk``): free-flow
first moves, query-time costs, ``-1``/unreachable and ``k_moves``
budget stops, ``plen`` = edges followed, pad lanes halted at birth.
Answers are bit-identical to the XLA path — pinned by
``tests/test_pallas_walk.py`` in interpret mode under the CPU tier-1
run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.env import env_cast, env_str
from ..utils.log import get_logger
from .device_graph import DeviceGraph
from .table_search import pick_buckets

log = get_logger(__name__)

#: accepted DOS_WALK_KERNEL spellings; anything else degrades to auto
WALK_KERNELS = ("auto", "pallas", "xla")

#: default per-core VMEM budget (MB) the fused kernel may claim for its
#: double-buffered row tile + resident graph tables; v5e exposes ~16 MB
#: and the compiler needs headroom for the walk state itself
_VMEM_BUDGET_MB = 10.0


def walk_kernel_choice() -> str:
    """The raw ``DOS_WALK_KERNEL`` knob: ``auto`` / ``pallas`` /
    ``xla``; malformed values degrade to ``auto`` with a log line
    (the shared ``utils.env`` policy)."""
    raw = (env_str("DOS_WALK_KERNEL", "auto") or "auto").strip().lower()
    if raw not in WALK_KERNELS:
        log.warning("ignoring malformed DOS_WALK_KERNEL=%r (using "
                    "'auto'; valid: %s)", raw, "/".join(WALK_KERNELS))
        return "auto"
    return raw


def resolve_walk_kernel() -> str:
    """Resolve the knob to a concrete kernel: ``auto`` is the XLA walk
    on every backend (see the module docstring for why not Pallas on
    a TPU)."""
    choice = walk_kernel_choice()
    return "xla" if choice == "auto" else choice


class WalkKernelUnavailable(RuntimeError):
    """An explicitly requested walk kernel cannot run at this shape."""


def pallas_walk_fits(n: int, k: int, m: int, q: int,
                     n_buckets: int = 0,
                     codec: str = "raw") -> tuple[bool, str]:
    """Would the fused kernel's VMEM working set fit the budget?

    ``n``/``k``/``m`` are the graph's node count, max out-degree, and
    edge count; ``q`` the (padded) batch size. The working set counts
    what the kernel actually holds live per bucket: the double-buffered
    row tile (int8 ``2 * qb * n``, HALVED to nibble width under
    ``codec="pack4"`` — the compressed working set, ROADMAP item 1)
    PLUS the loop-resident int32 widening of the active slot
    (``tl = ...astype(int32)`` — 4 bytes/lane, the dominant consumer;
    the pack4 unpack holds one extra int32 byte-gather temp of the same
    size while it widens), and the graph tables both as staged blocks
    and as their flattened loop copies. Returns ``(ok, reason)``.
    """
    if q <= 0:
        return True, ""
    nb = pick_buckets(q, n_buckets)
    qb = q // nb
    if codec == "pack4":
        tile = 2 * qb * ((n + 1) // 2)         # uint8 nibbles, 2 slots
        unpack_tmp = 4 * qb * n                # int32 byte-gather temp
    else:
        tile = 2 * qb * n                      # int8 rows, two slots
        unpack_tmp = 0
    tile_widened = 4 * qb * n                  # int32 active-slot copy
    # nbr + eid + w_pad int32, staged block + flattened loop copy
    tables = 2 * (2 * n * k * 4 + (m + 1) * 4)
    budget_mb = env_cast("DOS_WALK_VMEM_MB", _VMEM_BUDGET_MB, float)
    if budget_mb <= 0:
        budget_mb = _VMEM_BUDGET_MB
    need = tile + tile_widened + unpack_tmp + tables
    if need > budget_mb * 2**20:
        return False, (
            f"fused-walk working set {need / 2**20:.1f} MB "
            f"({codec} tile 2x{qb} rows + int32 widening + tables) over "
            f"the {budget_mb:.0f} MB VMEM budget (DOS_WALK_VMEM_MB)")
    return True, ""


#: why an explicit ``pallas`` request is refused on a TPU: what the
#: chip's compiler answered for a described v5e (the strict xfail in
#: ``tests/test_chip_compile.py`` notices when it stops refusing)
TPU_REFUSAL = ("the TPU compiler refuses the fused walk: Mosaic's "
               "gather lowering rejects its in-VMEM gathers, and its "
               "(1, qb) bucket blocks break the (8, 128) tiling rule")


def choose_walk_kernel(n: int, k: int, m: int, q: int,
                       codec: str = "raw") -> str:
    """The one selection site both serving paths call: resolve the
    ``DOS_WALK_KERNEL`` knob for a batch of ``q`` queries on a graph of
    ``n`` nodes, max out-degree ``k`` and ``m`` edges. ``codec`` names
    the tile the kernel would stage (``pack4`` = the compressed-
    resident nibble tile). A ``pallas`` request that cannot run raises
    :class:`WalkKernelUnavailable` with the reason."""
    kernel = resolve_walk_kernel()
    if kernel != "pallas":
        return kernel
    if jax.default_backend() == "tpu":
        raise WalkKernelUnavailable(
            f"DOS_WALK_KERNEL=pallas: {TPU_REFUSAL}")
    fits, why = pallas_walk_fits(n, k, m, q, codec=codec)
    if not fits:
        raise WalkKernelUnavailable(f"DOS_WALK_KERNEL=pallas: {why}")
    return "pallas"


# ----------------------------------------------------- row-tile loaders
#
# THE SEAM: one fm row -> one VMEM tile lane. Everything the walk knows
# about where rows come from lives in these two functions. The
# compressed-CPD tier (ROADMAP item 1) uses them unchanged: under
# ``packed4`` the "row" being staged is the pack4 NIBBLE row (the tile
# narrows to ceil(n/2) uint8), and decompression happens after the
# stage — an on-chip nibble unpack where the raw path only widens to
# int32 — so the walk loop below never changes.

#: pack4 marker nibble for -1 (the streamed wire format's vocabulary,
#: models.resident.PACK4_MARKER — duplicated: ops must not import
#: models)
_PACK4_MARKER = 15

def _stage_row_direct(fm_ref, tile, j, row):
    """Interpret-mode loader: plain ref copy (TPU DMA semaphores do not
    exist under the Pallas interpreter)."""
    tile[j, :] = fm_ref[row, :]


def _stage_row_dma(fm_ref, tile, sem, slot, j, row, wait: bool):
    """Hardware loader: async HBM->VMEM copy of one row into tile slot
    ``slot``, lane ``j``. ``wait=False`` starts the copy (the double
    buffer's prefetch half), ``wait=True`` blocks on it."""
    cp = pltpu.make_async_copy(fm_ref.at[row], tile.at[slot, j],
                               sem.at[slot])
    if wait:
        cp.wait()
    else:
        cp.start()


def _make_kernel(nb: int, qb: int, n: int, k: int, limit: int,
                 unroll: int, budget: int | None, use_dma: bool,
                 packed4: bool):
    """Build the per-bucket kernel body (static shapes baked in).

    ``budget`` is the per-step ``k_moves`` cap (None = the unlimited
    reference default — the compare vanishes from the program, same
    static specialization as the XLA kernel's). ``packed4``: the fm
    ref holds pack4 nibble rows (``models.resident``) — the staging
    copies move the HALF-width uint8 rows and the widening step
    becomes decompress-on-tile (nibble unpack, 15 -> -1).
    """

    def _stage_bucket(rows_sref, fm_ref, tile, sem, slot, base,
                      wait: bool):
        # one loader call per lane; rows arrive via scalar prefetch so
        # the indices exist before the bucket's compute does
        def stage(j, _):
            row = rows_sref[base + j]
            if use_dma:
                _stage_row_dma(fm_ref, tile, sem, slot, j, row, wait)
            else:
                _stage_row_direct(fm_ref, tile, j, row)
            return 0

        jax.lax.fori_loop(0, qb, stage, 0)

    def widen(staged):
        """Staged tile slot -> the int32 [qb, n] slot table the walk
        gathers from. Raw tiles only widen; pack4 tiles DECOMPRESS
        here — a byte gather + nibble shift per column, the on-chip
        half of the compressed-resident scheme."""
        if not packed4:
            return staged.astype(jnp.int32)
        pk = staged.astype(jnp.int32)                  # [qb, ceil(n/2)]
        cols = jnp.arange(n, dtype=jnp.int32)
        byte = jnp.take(pk, cols // 2, axis=1)         # [qb, n]
        v = (byte >> ((cols % 2) * 4)) & 0xF
        return jnp.where(v == _PACK4_MARKER, jnp.int32(-1), v)

    def kernel(rows_sref, s_ref, t_ref, valid_ref, fm_ref, nbr_ref,
               eid_ref, w_ref, cost_ref, plen_ref, fin_ref, tile,
               *dma_scratch):
        i = pl.program_id(0)
        if use_dma:
            # double buffer: program 0 stages its own tile; every
            # program then prefetches bucket i+1 into the spare slot
            # BEFORE walking, so the next bucket's rows stream in
            # behind this bucket's compute
            (sem,) = dma_scratch
            cur = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i == 0)
            def _():
                _stage_bucket(rows_sref, fm_ref, tile, sem, 0, 0,
                              wait=False)

            @pl.when(i + 1 < nb)
            def _():
                _stage_bucket(rows_sref, fm_ref, tile, sem, nxt,
                              (i + 1) * qb, wait=False)

            _stage_bucket(rows_sref, fm_ref, tile, sem, cur, i * qb,
                          wait=True)
            tl = widen(tile[cur])                          # [qb, n]
        else:
            sem = None
            _stage_bucket(rows_sref, fm_ref, tile, sem, 0, i * qb,
                          wait=False)
            tl = widen(tile[...])                          # [qb, n]

        s_v = s_ref[0, :]
        t_v = t_ref[0, :]
        vld = valid_ref[0, :]
        # graph tables resident in VMEM for the whole walk (flattened
        # once: the per-step gather is nbr/eid[x * k + slot])
        nbr_f = nbr_ref[...].reshape(-1)
        eid_f = eid_ref[...].reshape(-1)
        w_f = w_ref[...].reshape(-1)

        def fm_slot(x):
            # the fused first-move gather: lane j reads ITS row's slot
            # from the staged tile — VMEM, never HBM, never XLA gather
            return jnp.take_along_axis(tl, x[:, None], axis=1)[:, 0]

        # same birth rule as the XLA scaffold: pad lanes start at t
        # (zero-length) and halted; real lanes halt on a -1 first move
        x0 = jnp.where(vld, s_v, t_v)
        halted0 = (fm_slot(x0) < 0) | ~vld
        state0 = (jnp.int32(0), x0, x0 * 0, x0 * 0, halted0)

        def cond(state):
            it, _, _, _, halted = state
            return (~jnp.all(halted)) & (it < limit)

        def step(x, cost, plen, halted):
            slot = fm_slot(x)
            can = (~halted) & (slot >= 0)
            if budget is not None:
                can &= plen < budget
            flat = x * k + jnp.maximum(slot, 0)
            # query-time weight application, fused into the walk: the
            # diffed w_pad is gathered per step, moves stay free-flow
            wt = jnp.take(w_f, jnp.take(eid_f, flat))
            cost = jnp.where(can, cost + wt, cost)
            plen = jnp.where(can, plen + 1, plen)
            x = jnp.where(can, jnp.take(nbr_f, flat), x)
            halted = halted | ~can
            return x, cost, plen, halted

        def body(state):
            it, x, cost, plen, halted = state
            for _ in range(unroll):
                x, cost, plen, halted = step(x, cost, plen, halted)
            return it + unroll, x, cost, plen, halted

        _, x, cost, plen, _ = jax.lax.while_loop(cond, body, state0)
        fin = (x == t_v) & vld
        cost_ref[0, :] = jnp.where(vld, cost, 0)
        plen_ref[0, :] = jnp.where(vld, plen, 0)
        fin_ref[0, :] = fin

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("k_moves", "max_steps", "unroll",
                                    "n_buckets", "interpret", "packed4"))
def _pallas_walk(dg: DeviceGraph, fm, t_rows, s, t, w_query_pad, valid,
                 k_moves: int, max_steps: int, unroll: int,
                 n_buckets: int, interpret: bool, packed4: bool):
    q = s.shape[0]
    n = dg.n
    k = dg.k
    limit = n if max_steps == 0 else max_steps
    unlimited = k_moves < 0 and max_steps == 0
    budget = None if unlimited else (limit if k_moves < 0 else k_moves)
    nb = n_buckets
    qb = q // nb

    rows32 = t_rows.astype(jnp.int32)
    s2 = s.astype(jnp.int32).reshape(nb, qb)
    t2 = t.astype(jnp.int32).reshape(nb, qb)
    v2 = valid.reshape(nb, qb)
    w2 = w_query_pad.astype(jnp.int32).reshape(1, -1)

    kernel = _make_kernel(nb, qb, n, k, limit, unroll, budget,
                          use_dma=not interpret, packed4=packed4)
    # the staged tile matches the fm row width: full int8 rows raw,
    # half-width uint8 nibble rows under pack4 residency
    width = int(fm.shape[1])
    tile_shape = ((2, qb, width) if not interpret else (qb, width))
    scratch = [pltpu.VMEM(tile_shape, fm.dtype)]
    if not interpret:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))

    bucket_spec = pl.BlockSpec((1, qb), lambda i, sref: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            bucket_spec,                                   # s
            bucket_spec,                                   # t
            bucket_spec,                                   # valid
            pl.BlockSpec(memory_space=pl.ANY),             # fm (HBM)
            pl.BlockSpec((n, k), lambda i, sref: (0, 0)),  # out_nbr
            pl.BlockSpec((n, k), lambda i, sref: (0, 0)),  # out_eid
            pl.BlockSpec((1, w2.shape[1]),
                         lambda i, sref: (0, 0)),          # w_query_pad
        ],
        out_specs=[bucket_spec, bucket_spec, bucket_spec],
        scratch_shapes=scratch,
    )
    # under shard_map the outputs vary over the mesh axes the queries
    # vary over (check_vma needs it said)
    vma = jax.typeof(s2).vma
    cost, plen, fin = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, qb), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((nb, qb), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((nb, qb), jnp.bool_, vma=vma),
        ],
        interpret=interpret,
    )(rows32, s2, t2, v2, fm, dg.out_nbr, dg.out_eid, w2)
    return cost.reshape(q), plen.reshape(q), fin.reshape(q)


def pallas_walk_batch(dg: DeviceGraph, fm, t_rows, s, t, w_query_pad,
                      valid=None, k_moves: int = -1, max_steps: int = 0,
                      unroll: int = 8, n_buckets: int = 0,
                      interpret: bool | None = None,
                      packed4: bool = False):
    """Fused-kernel drop-in for
    :func:`.table_search.table_search_batch` — same parameters, same
    ``(cost, plen, finished)`` contract, bit-identical answers.

    ``interpret``: None = auto (interpret everywhere but real TPU —
    how the CPU tier-1 parity suite executes the kernel); the
    remaining knobs mirror the XLA kernel's and share
    :func:`.table_search.pick_buckets` as the grid resolver.

    ``packed4``: ``fm`` is the pack4-compressed resident shard
    (``[R, ceil(N/2)]`` uint8 nibble rows, ``models.resident``); the
    row-tile loader stages the packed rows and the kernel unpacks
    on-chip — decompress inside the staging DMA, the compressed
    working set :func:`pallas_walk_fits` accounts under
    ``codec="pack4"``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q = s.shape[0]
    if q == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), jnp.bool_)
    if valid is None:
        valid = jnp.ones((q,), jnp.bool_)
    return _pallas_walk(dg, fm, t_rows, s, t, w_query_pad, valid,
                        int(k_moves), int(max_steps), int(unroll),
                        pick_buckets(q, int(n_buckets)),
                        bool(interpret), bool(packed4))
