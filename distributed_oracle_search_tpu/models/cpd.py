"""The CPD oracle model: sharded build, persistence, routed batched query.

This is the framework's flagship "model": the Compressed Path Database —
a ``[W, R, N]`` int8 first-move tensor (worker × owned-target-row × node),
axis 0 sharded over the mesh's ``worker`` axis. It bundles the three phases
the reference spreads over ``make_cpd_auto`` / CPD block files /
``fifo_auto`` (SURVEY.md §3):

* ``build()``   — sharded batched min-plus Bellman-Ford (reference: per-node
                  Dijkstra sweeps per worker, ``README.md:95``),
* ``save()`` / ``load()`` — per-(worker, block) ``.npy`` files + an
  ``index.json`` manifest. The CPD index *is* the system checkpoint: build
  once, serve statelessly, reload on restart (reference ``README.md:35,92``,
  ``make_fifos.py:21``; SURVEY.md §5 checkpoint/resume). Blocks follow the
  controller's ``bid``/``bidx`` scheme, so a partial build can resume at
  block granularity.
* ``query()``   — routes each (s, t) to the shard owning t (the invariant of
                  ``process_query.py:56-57``), walks all queries in one XLA
                  call, and scatters results back to input order.

On HBM the table is deliberately **uncompressed** — the reference's
run-length compression trades lookups for pointer chasing, which is exactly
wrong for TPU; sharding is the compression here (SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import contextlib
import functools
import glob
import io
import json
import os
import queue
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph, INF
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..ops import DeviceGraph
from ..parallel.mesh import (
    make_mesh, make_worker_mesh, worker_sharding,
    WORKER_AXIS, DATA_AXIS, LANE_AXIS,
)
from ..parallel.partition import DistributionController
from ..parallel.sharded import (
    build_fm_lanes, build_tables_multi_sharded, build_tables_sharded,
    pad_targets, build_fm_sharded, query_dist_sharded, query_mat_sharded,
    query_multi_sharded, query_paths_sharded, query_sharded,
    query_tables_multi_sharded, query_tables_sharded,
)
from ..testing import faults
from ..utils.atomicio import (
    SWEEP_MIN_AGE_S, TMP_SUFFIX, AtomicNpyWriter, atomic_copy_file,
    atomic_save_npy, atomic_write_json, digest_bytes, digest_file,
    quarantine,
)
from ..utils.env import env_cast, env_flag
from ..utils.log import get_logger
from .resident import (
    block_codec, encode_block, is_container, maybe_decode_rows,
    resident_choice,
)

log = get_logger(__name__)

#: manifest schema version. v2 adds per-block content digests + shapes
#: (``blocks``) and ``digest_algo``; readers tolerate unknown keys, so a
#: bump is MAJOR only when existing keys change meaning — v1 indexes
#: load under v2 code, v(N+1) indexes are rejected by vN code.
INDEX_VERSION = 2

# artifact-durability counters: every verify/quarantine/rebuild/resume
# event in the index data plane proves it fired through one of these
M_BLOCKS_VERIFIED = obs_metrics.counter(
    "cpd_blocks_verified_total",
    "CPD blocks that passed load-time digest/shape verification")
M_BLOCKS_CORRUPT = obs_metrics.counter(
    "cpd_blocks_corrupt_total",
    "CPD blocks found missing/torn/digest-mismatched at load or verify")
M_BLOCKS_REBUILT = obs_metrics.counter(
    "cpd_blocks_rebuilt_total",
    "corrupt CPD blocks rebuilt in place from the graph")
M_BLOCKS_RESUMED = obs_metrics.counter(
    "build_blocks_resumed_total",
    "blocks skipped by a resumed build (ledger-verified complete)")
M_REPLICA_MISMATCH = obs_metrics.counter(
    "replica_digest_mismatches_total",
    "replica blocks whose digest diverged from the primary's "
    "(anti-entropy pass; quarantined + healed)")
M_REPLICA_COPIED = obs_metrics.counter(
    "replica_blocks_copied_total",
    "replica blocks materialized by copying a digest-valid primary "
    "block instead of recomputing from the graph")
M_BLOCKS_ADOPTED = obs_metrics.counter(
    "reshard_blocks_adopted_total",
    "blocks digest-verified (healing as needed) by a worker adopting "
    "shard ownership during a membership reconfiguration")

# build-pipeline + delta-build series: the throughput plane of the
# road-scale build (ROADMAP item 1) — staging overlap, pipeline stalls,
# and how much work an epoch-keyed delta rebuild actually skipped
M_ROWS_STAGED = obs_metrics.counter(
    "build_rows_staged_total",
    "CPD build rows whose frontier/target inputs the host stager "
    "prepared (pipelined and serial builds both count)")
M_STAGE_OVERLAP = obs_metrics.histogram(
    "build_stage_overlap_seconds",
    "host-side staging time per block (target pad + device upload + "
    "pre-opened block writer); overlapped with device compute when "
    "the pipeline is on — overlap WON, so more is better")
M_PIPE_STALL = obs_metrics.histogram(
    "build_pipeline_stall_seconds",
    "time the build's device-dispatch loop waited for the host stager "
    "(pipelined builds only; the number the async stager exists to "
    "drive to zero)")
M_DELTA_ROWS = obs_metrics.counter(
    "build_delta_rows_recomputed_total",
    "rows a delta rebuild recomputed because the changed-edge pass "
    "marked their first-move entries dirty")
M_DELTA_SKIPPED = obs_metrics.counter(
    "build_delta_skipped_blocks_total",
    "blocks a delta rebuild reused (byte copy from the old index, "
    "digest journaled) instead of recomputing")
M_MESH_COLLECTIVE = obs_metrics.histogram(
    "mesh_collective_seconds",
    "on-mesh collective join per mat-family row (query_mat: walk + "
    "scatter + psum, replacing the head-side fan-out/join)")

# the mesh oracle's query stages (CPDOracle.query / query_multi): one
# observation per call, on spans of the same names (cpd.route,
# cpd.walk, cpd.fetch) tagged pairs= and lanes=
M_ROUTE = obs_metrics.histogram(
    "cpd_route_seconds",
    "mesh query: host routing of the pairs to their owners' lanes "
    "(group by owner, order by length estimate, pad)")
M_WALK = obs_metrics.histogram(
    "cpd_walk_seconds",
    "mesh query: routed pairs and weights to the devices, the walk's "
    "launch, until every shard's answers are ready")
M_FETCH = obs_metrics.histogram(
    "cpd_fetch_seconds",
    "mesh query: answers to the host and back in input order")
M_PAIRS = obs_metrics.counter(
    "cpd_pairs_total", "pairs the mesh oracle answered")
M_LANES = obs_metrics.counter(
    "cpd_lanes_total",
    "lanes the mesh walks ran, padding included (each worker's group "
    "is padded to a power of two)")

#: compressed device->host fm fetch below this raw size is not worth the
#: extra device round trip (the count pass) — plain fetch instead
FETCH_RLE_MIN_BYTES = 16 << 20


@jax.jit
def _fm_run_count(fm: jnp.ndarray) -> jnp.ndarray:
    """Number of target-axis runs in a [C, N] fm block (column-major
    over the transposed layout — the same coherence the streamed wire
    format exploits: ~93-97% of entries equal the entry one target up).
    """
    c = fm.shape[0]
    flat = fm.T.reshape(-1)
    ch = jnp.concatenate([jnp.ones(1, jnp.bool_),
                          flat[1:] != flat[:-1]])
    ch = ch | ((jnp.arange(flat.shape[0]) % c) == 0)
    return ch.sum()


def _fm_rle_encode_impl(fm: jnp.ndarray, cap: int):
    """Device-side transposed RLE of a [C, N] fm block ->
    ``(lens uint16 [cap], vals int8 [cap])`` in column-major run order
    (pads: length 0). Runs break at column boundaries, so a run never
    exceeds C (uint16-safe for C <= 65535; callers gate)."""
    c = fm.shape[0]
    flat = fm.T.reshape(-1)
    total = flat.shape[0]
    ch = jnp.concatenate([jnp.ones(1, jnp.bool_),
                          flat[1:] != flat[:-1]])
    ch = ch | ((jnp.arange(total) % c) == 0)
    idx = jnp.nonzero(ch, size=cap, fill_value=total)[0].astype(jnp.int32)
    vals = flat[jnp.minimum(idx, total - 1)]
    nxt = jnp.concatenate([idx[1:],
                           jnp.full((1,), total, jnp.int32)])
    return (nxt - idx).astype(jnp.uint16), vals


_fm_rle_encode = functools.partial(
    jax.jit, static_argnames=("cap",))(_fm_rle_encode_impl)
#: donating variant for the pipelined build: the encode is the LAST
#: consumer of a block's fm buffer, and donating it releases that HBM
#: immediately instead of holding it live under the next block's kernels
#: (real backends only — CPU donation is unimplemented and would warn
#: per call; selection in fetch_fm)
_fm_rle_encode_donate = functools.partial(
    jax.jit, static_argnames=("cap",),
    donate_argnums=(0,))(_fm_rle_encode_impl)


def _fetch_rle_eligible(shape) -> bool:
    c, n = shape
    return (env_flag("DOS_FETCH_RLE", True) and c >= 2
            and c <= 65535 and c * n >= FETCH_RLE_MIN_BYTES)


def fetch_fm(dev, count_dev=None, donate: bool = False) -> np.ndarray:
    """Device [C, N] int8 fm block -> host numpy, RLE-compressed over
    the wire when it pays.

    fm rows run 14-34 long along the target axis, so the device
    encodes the transposed block (~3 bytes per run) and the host
    expands with one ``np.repeat`` — typically 5-15x fewer bytes
    drained to the host. Whether that still pays against a plain fetch
    on a chip attached to its host is an open question (ROADMAP). Falls back to a plain fetch for small
    blocks, incompressible blocks, and ``DOS_FETCH_RLE=0``.

    ``count_dev``: optionally the ``_fm_run_count(dev)`` result
    dispatched EAGERLY when the block was computed — pipelined callers
    (``build_worker_shard``) enqueue it right behind the build kernel
    so this fetch never waits on later-dispatched device work for the
    count.

    ``donate=True`` (build callers that never touch ``dev`` again):
    the RLE encode — this buffer's last consumer — DONATES it on real
    backends, so a drained block's fm HBM frees under the next block's
    kernels instead of doubling the pipeline's working set. The
    default keeps the caller's buffer valid: donation is the caller's
    decision, never a buried env check that invalidates someone
    else's array."""
    c, n = dev.shape
    if not _fetch_rle_eligible((c, n)):
        return np.asarray(dev)
    n_runs = int(_fm_run_count(dev) if count_dev is None else count_dev)
    cap = 1 << max(n_runs - 1, 0).bit_length()
    if 3 * cap >= c * n:          # incompressible: plain wins
        return np.asarray(dev)
    enc = (_fm_rle_encode_donate
           if donate and jax.default_backend() != "cpu"
           else _fm_rle_encode)
    lens, vals = enc(dev, cap)
    lens_h, vals_h = jax.device_get((lens, vals))
    flat = np.repeat(vals_h[:n_runs], lens_h[:n_runs].astype(np.int64))
    return np.ascontiguousarray(flat.reshape(n, c).T)


def _host(x) -> np.ndarray:
    """Sharded device result -> host numpy, multi-controller safe.

    Single-process: a plain ``np.asarray`` (device transfer of the local
    shards). With >1 JAX process the array spans non-addressable devices,
    so it rides ``process_allgather`` instead — every controller gets the
    identical global value, preserving the invariant that all processes
    compute the same campaign results."""
    if jax.process_count() > 1:
        from ..parallel.multihost import gather_to_host

        return gather_to_host(x)
    return np.asarray(x)


def _host_tree(tree):
    """Like :func:`_host` over a pytree — but single-process it fetches
    ALL leaves in ONE ``device_get`` (one round trip, not one per
    leaf)."""
    if jax.process_count() > 1:
        return jax.tree.map(_host, tree)
    return jax.device_get(tree)


def shard_block_name(wid: int, bid: int, replica: int = 0) -> str:
    """Block file name. ``replica=0`` (the primary copy) keeps the
    legacy name; replica rank r's copy — the SAME rows, hosted by worker
    ``(wid + r) % W`` — is a separate block set ``cpd-w<wid>-r<r>-b<bid>``
    so primaries and replicas verify/heal independently."""
    if replica:
        return f"cpd-w{wid:05d}-r{replica:02d}-b{bid:05d}.npy"
    return f"cpd-w{wid:05d}-b{bid:05d}.npy"


def block_file_replica(fname: str) -> int:
    """Replica rank encoded in a block file name (0 for primaries)."""
    parts = fname.split("-")
    if len(parts) >= 4 and parts[2].startswith("r"):
        return int(parts[2][1:])
    return 0


def ledger_path(outdir: str, wid: int, replica: int = 0) -> str:
    if replica:
        return os.path.join(outdir,
                            f"build-w{wid:05d}-r{replica:02d}.ledger")
    return os.path.join(outdir, f"build-w{wid:05d}.ledger")


class BuildLedger:
    """Per-worker build journal: one JSON line per completed,
    digest-valid block.

    The ledger is the crash-resume source of truth: a block counts as
    done only when its line is in the journal AND the file on disk still
    matches the recorded digest — a torn write, a swept tmp file, or
    bit-rot all fail the check and the block is recomputed. Appends are
    flushed+fsynced per line; a torn trailing line (crash mid-append)
    is skipped on read, costing at most one block's recompute. Later
    entries for the same file win, so a rebuilt block just appends."""

    def __init__(self, outdir: str, wid: int, replica: int = 0):
        self.path = ledger_path(outdir, wid, replica)

    def entries(self) -> dict[str, dict]:
        out: dict[str, dict] = {}
        try:
            with open(self.path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        ent = json.loads(line)
                    except ValueError:
                        continue          # torn trailing append
                    if isinstance(ent, dict) and "file" in ent:
                        out[ent["file"]] = ent
        except OSError:
            pass
        return out

    def record(self, fname: str, digest: str, shape, dtype: str,
               epoch: int | None = None,
               codec: str | None = None) -> None:
        """Journal one completed block. ``epoch`` keys the line to a
        diff-epoch build (delta rebuilds and their full-degrade path):
        readers that resume an epoch-keyed build treat entries from any
        OTHER epoch as invalid — epoch-keyed block invalidation — while
        legacy readers simply ignore the unknown key (the codec
        contract). ``codec`` records a compressed block's encoding
        (``models.resident``) so the manifest harvest can carry it;
        raw blocks omit the key, keeping legacy ledgers byte-identical."""
        ent = {"file": fname, "digest": digest,
               "shape": list(shape), "dtype": dtype}
        if epoch is not None:
            ent["epoch"] = int(epoch)
        if codec is not None:
            ent["codec"] = str(codec)
        line = json.dumps(ent)
        with open(self.path, "a") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())


def _block_done(outdir: str, fname: str, entries: dict[str, dict],
                epoch: int | None) -> bool:
    """Resume check with epoch-keyed invalidation: a plain build
    (``epoch=None``) keeps :func:`block_complete`'s rules (legacy
    un-ledgered blocks accepted if they parse); an epoch-keyed build
    requires a ledger line carrying THAT epoch with a matching on-disk
    digest — a parseable block from another weight regime must never be
    skipped into the new index."""
    if epoch is None:
        return block_complete(outdir, fname, entries)
    ent = entries.get(fname)
    if ent is None or ent.get("epoch") != int(epoch):
        return False
    path = os.path.join(outdir, fname)
    try:
        return digest_file(path) == ent.get("digest")
    except OSError:
        return False


def block_complete(outdir: str, fname: str,
                   ledger_entries: dict[str, dict]) -> bool:
    """Is an on-disk block safe to skip on resume? Ledgered blocks must
    match their recorded digest; pre-ledger (legacy) blocks must at
    least parse as a ``.npy`` — a torn legacy write fails the header or
    size check and is rebuilt."""
    path = os.path.join(outdir, fname)
    if not os.path.exists(path):
        return False
    ent = ledger_entries.get(fname)
    if ent is not None:
        return digest_file(path) == ent.get("digest")
    try:
        np.load(path, mmap_mode="r")
        return True
    except Exception as e:  # noqa: BLE001 — any unreadable file means
        # rebuild; say which file and why, or the operator sees an
        # unexplained non-skip on every resume
        log.debug("unledgered block %s unreadable (%s); rebuilding",
                  fname, e)
        return False


@contextlib.contextmanager
def _stage(name: str, hist, pairs: int, lanes: int):
    """A mesh query stage: span ``name`` tagged ``pairs=`` and
    ``lanes=``, and its time observed into ``hist``."""
    t0 = time.perf_counter()
    with obs_trace.span(name, pairs=pairs, lanes=lanes):
        yield
    hist.observe(time.perf_counter() - t0)


def length_estimate(graph: Graph, s: np.ndarray, t: np.ndarray):
    """Cheap host-side walk-length predictor: L1 coordinate distance
    (road networks keep path length ~monotone in it). Zero device work;
    used only to ORDER queries so the bucketed walk groups similar
    lengths — never affects answers. Shared by the resident and streamed
    serving paths."""
    xs, ys = graph.xs, graph.ys
    return np.abs(xs[s] - xs[t]) + np.abs(ys[s] - ys[t])


#: shift coverage below which auto falls back to the ELL gather relaxation
SHIFT_COVERAGE_MIN = 0.9

#: lattice-edge share below which auto will not pick the fast-sweeping
#: build (shift planes keep sweep correct on any graph, but only lattice
#: edges benefit from the quadrant scans)
SWEEP_COVERAGE_MIN = 0.75

#: below this node count the per-hop shift relaxation beats the sweep's
#: scan overhead (measured crossover ~25k nodes on v5e)
SWEEP_MIN_NODES = 32_768

#: modeled ELL+COO split cost ratio below which auto prefers the split
#: over the plain padded-ELL gather (degree-skewed graphs: road networks
#: pad K to the max degree while the mean is ~4)
ELLSPLIT_RATIO_MAX = 0.75

#: below this node count the dense kernels' full sweeps are cheap enough
#: that the frontier queue's per-pop overhead does not pay
FRONTIER_MIN_NODES = 32_768

#: minimum edge id-locality (ops.frontier_relax.locality_fraction) for
#: the delta-stepping frontier build: under it the union wavefront of a
#: clustered target batch degenerates to the whole graph (measured 0.4-
#: 0.6 after RCM/BFS reorder vs 0.02 on shuffled ids)
FRONTIER_LOCALITY_MIN = 0.25


def pick_build_kernel(graph: Graph, method: str = "auto"):
    """Resolve the build-method knob to ``(kind, structure)``.

    ``kind`` ∈ {"sweep", "shift", "frontier", "ellsplit", "ell"};
    ``structure`` is the matching host-side bundle (GridGraph /
    ShiftGraph / FrontierGraph / ELLSplitGraph / None). The coverage
    decisions happen on host-side split arrays — graphs that fall back
    never pay a device transfer.

    ``auto`` picks the fast-sweeping build for large grid-structured
    graphs (O(cycles) not O(hop-diameter) — the only build that scales to
    the 100k+-node regime), the shift relaxation for smaller or
    non-lattice-but-banded graphs, the delta-stepping frontier queue for
    large locality-ordered irregular graphs (road networks after
    BFS/RCM reorder — the only irregular build whose work tracks the
    frontier instead of N x diameter), the ELL+COO split for the
    remaining degree-skewed irregular graphs, and the padded-ELL gather
    otherwise.
    """
    from ..ops.device_graph import JINF
    from ..ops.ell_split import ell_split_graph, split_ratio
    from ..ops.frontier_relax import frontier_graph, locality_fraction
    from ..ops.grid_sweep import GridGraph
    from ..ops.shift_relax import ShiftGraph, split_coverage

    if method not in ("auto", "ell", "ellsplit", "frontier", "shift",
                      "sweep"):
        raise ValueError(f"unknown build method {method!r}")
    if method == "ell":
        return "ell", None
    if method == "frontier":
        return "frontier", frontier_graph(graph)
    if method == "ellsplit":
        _, k0 = split_ratio(np.diff(graph.out_ptr), graph.max_out_degree)
        return "ellsplit", ell_split_graph(graph, k0=k0)
    if method in ("auto", "sweep"):
        split = graph.grid_split()
        if split is not None:
            if method == "sweep":
                return "sweep", GridGraph(*split)
            # lattice share from the HOST arrays (no device transfer for
            # graphs the gate rejects): what the quadrant scans serve
            _, _, wl, wr, wd, wu, _, w_shift, src_left, _, _ = split
            on_grid = sum(int((np.asarray(a) < int(JINF)).sum())
                          for a in (wl, wr, wd, wu))
            total = (on_grid + int((np.asarray(w_shift) < int(JINF)).sum())
                     + len(src_left))
            if (total and on_grid / total >= SWEEP_COVERAGE_MIN
                    and graph.n >= SWEEP_MIN_NODES):
                return "sweep", GridGraph(*split)
        elif method == "sweep":
            raise ValueError("method='sweep' but no grid layout fits "
                             "(Graph.grid_split returned None)")
    shifts, w_shift, nbr_left, w_left = graph.shift_split()
    if method == "auto" and split_coverage(w_shift,
                                           w_left) < SHIFT_COVERAGE_MIN:
        # irregular graph: the frontier queue when ids have locality
        # (post-reorder road networks — its work tracks the wavefront,
        # not N x diameter), else split the padded ELL when the degree
        # skew makes it worthwhile (cost model in ops.ell_split)
        if (graph.n >= FRONTIER_MIN_NODES
                and locality_fraction(graph) >= FRONTIER_LOCALITY_MIN):
            return "frontier", frontier_graph(graph)
        ratio, k0 = split_ratio(np.diff(graph.out_ptr),
                                graph.max_out_degree)
        if ratio <= ELLSPLIT_RATIO_MAX:
            return "ellsplit", ell_split_graph(graph, k0=k0)
        return "ell", None
    return "shift", ShiftGraph(shifts, w_shift, nbr_left, w_left, graph.n)


# ------------------------------------------------------- build pipeline

def build_pipeline_enabled() -> bool:
    """``DOS_BUILD_PIPELINE`` (default on): stage the next block's
    inputs on a background thread while the device runs the current
    one. Off = the serial reference loop (the parity smoke pins the
    two bit-identical)."""
    return env_flag("DOS_BUILD_PIPELINE", True)


def build_stage_depth() -> int:
    """``DOS_BUILD_STAGE_DEPTH`` (default 2): staged blocks the host
    keeps prepared ahead of the device — each holds its padded target
    uploads and a pre-opened block writer, so depth is bounded host
    memory, not correctness."""
    return max(env_cast("DOS_BUILD_STAGE_DEPTH", 2, int), 1)


def build_chunk_rows(graph: Graph, chunk: int, n_owned: int,
                     kind: str = "ell") -> int:
    """Rows per build kernel call. An explicit ``chunk`` wins; with
    ``chunk=0`` and ``DOS_BUILD_HBM_MB`` set, the chunk is sized to
    that HBM budget from the kernel's per-row working-set estimate —
    multi-row frontier batching: the frontier/relax kernels amortize
    their fixed per-dispatch cost (loop floor + host sync) over as many
    source rows as the budget fits instead of
    dispatching row by row. Power-of-two floored for stable compiled
    shapes across shards; ``DOS_BUILD_HBM_MB`` unset keeps the legacy
    whole-shard batch."""
    if chunk > 0:
        return chunk
    budget_mb = env_cast("DOS_BUILD_HBM_MB", 0.0, float)
    if budget_mb <= 0:
        return max(n_owned, 1)
    k = max(graph.max_out_degree, 1)
    # dominant live arrays per target row: the dense gather's [N, K, B]
    # relax temp (ell/ellsplit) or dist + temp + wake planes (~3x int32)
    per_row = graph.n * ((k + 2) * 4 if kind in ("ell", "ellsplit")
                         else 12)
    rows = int(budget_mb * 1e6) // max(per_row, 1)
    rows = max(min(rows, max(n_owned, 1)), 1)
    return 1 << (int(rows).bit_length() - 1)


def _make_chunk_compute(dg, kind: str, structure, max_iters: int,
                        mesh=None):
    """One dispatch closure per resolved build kernel: takes a padded
    int32 target array (host or pre-uploaded device) and returns the
    ASYNC device fm block plus its eagerly dispatched RLE run count —
    the shared compute unit of the full build loop and the delta
    rebuild's row splice.

    ``mesh``: a worker-local lane mesh (``make_worker_mesh``) routes
    each chunk through :func:`~..parallel.sharded.build_fm_lanes` — the
    chunk's target rows become per-device lanes, bit-identical rows in
    the same order. Callers gate on chunk divisibility by the lane
    count; the pad shape is fixed per build, so the gate is one check."""
    from ..ops import build_fm_columns
    from ..ops.ell_split import build_fm_columns_ellsplit
    from ..ops.frontier_relax import build_fm_columns_frontier
    from ..ops.grid_sweep import build_fm_columns_sweep
    from ..ops.shift_relax import build_fm_columns_shift

    def compute_dev(pad):
        if mesh is not None:
            return build_fm_lanes(dg, np.asarray(pad), mesh, kind,
                                  structure, max_iters=max_iters)
        if kind == "sweep":
            return build_fm_columns_sweep(dg, structure, pad,
                                          max_iters=max_iters)
        if kind == "shift":
            return build_fm_columns_shift(dg, structure, pad,
                                          max_iters=max_iters)
        if kind == "frontier":
            return build_fm_columns_frontier(dg, structure, pad,
                                             max_iters=max_iters)
        if kind == "ellsplit":
            return build_fm_columns_ellsplit(dg, structure, pad,
                                             max_iters=max_iters)
        return build_fm_columns(dg, jnp.asarray(pad),
                                max_iters=max_iters)

    def compute_with_count(pad):
        d = compute_dev(pad)
        cd = (_fm_run_count(d) if _fetch_rle_eligible(d.shape)
              else None)
        return d, cd

    return compute_with_count


class _BackgroundStager:
    """Bounded-depth background staging thread of the pipelined build:
    prepares block b+1's inputs (padded targets, device upload, the
    pre-opened atomic block writer) while the device runs block b.
    Iterating yields the staged items in order; the queue wait is the
    pipeline stall the stager exists to hide
    (``build_pipeline_stall_seconds``). ``close()`` stops the thread
    and aborts every staged-but-unconsumed writer, so error paths
    leave no tmp debris behind."""

    def __init__(self, bids, stage_fn, depth: int, wid: int):
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(list(bids), stage_fn),
            name=f"dos-build-stager-w{wid}", daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Stop-aware bounded put; False when close() raced it."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, bids, stage_fn) -> None:
        try:
            for bid in bids:
                if self._stop.is_set():
                    return
                item = stage_fn(bid)
                if not self._put(("item", item)):
                    item[-1].abort()      # writer never reaches the loop
                    return
        except BaseException as e:  # noqa: BLE001 — carried to the
            # consuming build loop, which re-raises it in caller context
            self._put(("err", e))
            return
        self._put(("done", None))

    def __iter__(self):
        while True:
            t0 = time.perf_counter()
            kind, val = self._q.get()
            M_PIPE_STALL.observe(time.perf_counter() - t0)
            if kind == "done":
                return
            if kind == "err":
                raise val
            yield val

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        while True:
            try:
                kind, val = self._q.get_nowait()
            except queue.Empty:
                break
            if kind == "item":
                val[-1].abort()


def build_worker_shard(graph: Graph, dc: DistributionController, wid: int,
                       outdir: str, chunk: int = 0, max_iters: int = 0,
                       resume: bool = True,
                       method: str = "auto", replica: int = 0,
                       epoch: int | None = None,
                       ctx: dict | None = None,
                       codec: str | None = None) -> list[str]:
    """Build and persist ONE worker's CPD block files on the local device.

    This is the host-mode build unit: the reference launches one
    ``make_cpd_auto`` per worker over ssh/tmux (``make_cpds.py:20-21``), each
    emitting per-block CPD files; here one process builds its worker's rows
    block-by-block with the batched min-plus kernel (gather-free shift
    relaxation when the id layout allows) and writes
    ``cpd-w<wid>-b<bid>.npy`` per block — each through a tmp+fsync+rename
    atomic write, journaled (file, digest, shape) in the per-worker build
    ledger. ``resume=True`` skips blocks the ledger records as complete
    AND whose on-disk digest still matches (legacy un-ledgered blocks are
    accepted if they parse) — mid-build restart granularity the reference
    lacks (SURVEY.md §5 checkpoint/resume), now safe against torn writes:
    a build killed mid-flush recomputes exactly the missing tail.

    ``replica``: build the rank-``replica`` REPLICA block set of shard
    ``wid`` (same rows, ``-r<replica>-`` file names, its own ledger) —
    the copy hosted by worker ``(wid + replica) % W``. The kernels are
    deterministic, so a recomputed replica is bit-identical to the
    primary; callers that have a digest-valid primary on the same
    filesystem should prefer :func:`copy_replica_blocks` first and let
    this recompute only what could not be copied.

    The loop is a SOFTWARE PIPELINE (``DOS_BUILD_PIPELINE``, default
    on): a host-side stager thread prepares the NEXT block's padded
    target inputs — device upload included — and pre-opens its atomic
    block writer while the device runs the CURRENT block's kernels and
    the main thread drains/writes the PREVIOUS one; the fm fetch
    donates its buffer into the RLE encode on real backends so a
    drained block's HBM frees under the next block's compute. Results
    are bit-identical to the serial loop (the ``build`` parity smoke
    pins it): staging changes WHEN inputs are prepared, never what the
    kernels compute. ``chunk=0`` with ``DOS_BUILD_HBM_MB`` set sizes
    the per-kernel-call row batch to that HBM budget
    (:func:`build_chunk_rows`).

    ``epoch``: key this build's ledger lines to a diff epoch (delta
    rebuilds): on resume, only blocks journaled under the SAME epoch
    with a matching digest are skipped — a parseable block from
    another weight regime is invalidated, not adopted. Callers that
    TIME the build (bench) pass ``resume=False`` so no journal parse
    lands inside the measured region.

    ``ctx``: an optional dict shared across calls caching the per-graph
    compute setup (DeviceGraph upload + build-kernel resolution + the
    worker lane mesh) — the same hoist as ``delta_build_index``'s
    ``_delta_compute_ctx``: a resident worker (or a bench timing the
    build) rebuilding repeatedly must not pay a CSR re-upload and
    kernel re-pick per call.

    ``codec``: persist blocks compressed (``models.resident``
    RLE/pack4 containers; None resolves ``DOS_CPD_RESIDENT``, whose
    ``raw`` default keeps the legacy byte-identical .npy rows). Each
    block encodes independently and degrades to raw when its rows are
    not viable; the ledger line and the manifest harvest record the
    codec that actually applied.

    With ``DOS_MESH_DEVICES`` > 1 the per-chunk kernel calls run
    lane-parallel on the worker's local mesh (per-device target lanes
    under ``shard_map``, :func:`~..parallel.sharded.build_fm_lanes`) —
    bit-identical blocks; a chunk the lane count does not divide falls
    back to the single-device compute with one log line.
    """
    os.makedirs(outdir, exist_ok=True)
    # sweep THIS worker's atomic-write debris from a killed build; the
    # dir-wide sweep belongs to the campaign/launcher (other workers may
    # be writing their own tmp files in this dir right now). Same age
    # gate as the dir-wide sweep: a young tmp file may be a live write
    # by a concurrent same-wid process (a respawned worker healing while
    # its hung predecessor still drains) — deleting it would turn that
    # process's rename into a crash
    now = time.time()
    tmp_stem = (f"cpd-w{wid:05d}-r{replica:02d}-b*" if replica
                else f"cpd-w{wid:05d}-b*")
    for p in glob.glob(os.path.join(
            outdir, f"{tmp_stem}{TMP_SUFFIX}.*")):
        try:
            if now - os.path.getmtime(p) >= SWEEP_MIN_AGE_S:
                os.remove(p)
        except OSError:
            pass
    owned = dc.owned(wid)
    bs = dc.block_size
    n_blocks = (len(owned) + bs - 1) // bs
    # only the missing blocks are computed — a restart after a partial
    # build pays exactly for what is not yet on disk, and "on disk"
    # means ledger-journaled with a matching digest, not merely named
    ledger = BuildLedger(outdir, wid, replica)
    entries = ledger.entries() if resume else {}
    missing, resumed = [], 0
    for bid in range(n_blocks):
        if resume and _block_done(
                outdir, shard_block_name(wid, bid, replica), entries,
                epoch):
            resumed += 1
        else:
            missing.append(bid)
    if resumed:
        M_BLOCKS_RESUMED.inc(resumed)
        log.info("worker %d build resume: %d/%d block(s) already "
                 "complete and digest-valid", wid, resumed, n_blocks)
    if not missing:
        return []
    # hoistable compute setup: graph upload, kernel pick, lane mesh —
    # cached in the caller's ctx so a repeat build (resident rebuild,
    # bench rep) re-dispatches kernels without re-staging any of it
    ctx = {} if ctx is None else ctx
    if ctx.get("graph") is not graph:
        ctx.clear()
        ctx["graph"] = graph
        ctx["kernel"] = pick_build_kernel(graph, method)
        ctx["dg"] = DeviceGraph.from_graph(graph)
        ctx["mesh"] = make_worker_mesh()
    elif ctx.get("method") not in (None, method):
        ctx["kernel"] = pick_build_kernel(graph, method)
    ctx["method"] = method
    kind, structure = ctx["kernel"]
    dg = ctx["dg"]
    mesh = ctx["mesh"]
    # compute granularity (device working set) is independent of the
    # file granularity: each block file is assembled from `chunk`-row
    # kernel calls, so a 16k-row block never forces a 16k-row device
    # batch; with DOS_BUILD_HBM_MB set the chunk is budget-sized
    chunk = build_chunk_rows(graph, chunk, len(owned), kind=kind)
    if mesh is not None and chunk % mesh.shape[LANE_AXIS]:
        log.warning("worker %d: chunk %d does not divide over %d mesh "
                    "lane(s); building single-device", wid, chunk,
                    mesh.shape[LANE_AXIS])
        mesh = None
    compute_with_count = _make_chunk_compute(dg, kind, structure,
                                             max_iters, mesh=mesh)
    # this build never touches a drained block's device buffers again,
    # so the fetch may donate them into the encode (DOS_BUILD_DONATE).
    # Lane-mesh builds skip donation: the drained block is a GSPMD
    # array sharded across lanes, not a single donatable device buffer
    donate = env_flag("DOS_BUILD_DONATE", True) and mesh is None

    def stage(bid: int):
        """Host-side prep of ONE block: padded target arrays uploaded
        to device (the H2D transfer overlaps the previous block's
        kernels under the pipeline) and the block's atomic writer
        pre-opened — all of it off the device-dispatch critical path."""
        t0 = time.perf_counter()
        blk = owned[bid * bs: min((bid + 1) * bs, len(owned))]
        lens, pads = [], []
        for i in range(0, len(blk), chunk):
            part = blk[i:i + chunk]
            pad = np.full(chunk, -1, np.int32)  # fixed shape -> 1 compile
            pad[:len(part)] = part
            # lane-mesh builds keep the host array: the shard_map's own
            # dispatch shards it over lanes (a single-device pre-upload
            # here would just bounce back through the host)
            pads.append(pad if mesh is not None else jax.device_put(pad))
            lens.append(len(part))
        fname = shard_block_name(wid, bid, replica)
        writer = AtomicNpyWriter(os.path.join(outdir, fname))
        M_ROWS_STAGED.inc(int(len(blk)))
        M_STAGE_OVERLAP.observe(time.perf_counter() - t0)
        return (bid, fname, lens, pads, writer)

    codec_req = resident_choice() if codec is None else codec

    def flush(entry) -> None:
        bid, fname, lens, devs, writer = entry
        # RLE-compressed fetch per chunk (plain for small blocks): fm
        # compresses 5-15x over the target axis (see fetch_fm). Run
        # counts were dispatched eagerly with each chunk's build, so
        # the count sync here never waits on the NEXT block's kernels;
        # the encode does queue behind them, but it is milliseconds of
        # device work.
        parts = [fetch_fm(d, count_dev=cd, donate=donate)
                 for d, cd in devs]
        trimmed = [p[:ln] for p, ln in zip(parts, lens)]
        arr = (trimmed[0] if len(trimmed) == 1
               else np.concatenate(trimmed))
        # compressed persistence (DOS_CPD_RESIDENT / the codec param):
        # the block lands as a self-describing container through the
        # SAME atomic writer — digest, ledger, heal, and replica copies
        # all operate on the container bytes
        enc = encode_block(arr, codec_req)
        if enc is not None:
            arr, blk_codec = enc
        else:
            blk_codec = None
        # atomic write (into the pre-opened tmp), then the ledger line:
        # a kill between the two leaves a complete un-journaled file
        # (the legacy-parse resume path accepts it); a kill MID-write
        # leaves only tmp debris
        digest = writer.commit(arr)
        ledger.record(fname, digest, arr.shape, str(arr.dtype),
                      epoch=epoch, codec=blk_codec)
        # chaos hook: DOS_FAULTS="crash-build;..." dies here, between
        # block flushes — the kill-mid-build resume test's trigger
        rule = faults.inject("crash-build", wid=wid)
        if rule is not None:
            if rule.mode == "exit":
                os._exit(faults.KILL_EXIT_CODE)
            raise RuntimeError("crash-build fault injected")

    pipelined = build_pipeline_enabled() and len(missing) > 1
    stager = (_BackgroundStager(missing, stage, build_stage_depth(), wid)
              if pipelined else None)
    staged_iter = iter(stager) if stager is not None \
        else (stage(bid) for bid in missing)
    written = []
    pending = None                          # one block in flight
    try:
        for item in staged_iter:
            try:
                devs = [compute_with_count(p) for p in item[3]]
                if pending is not None:
                    flush(pending)
            except BaseException:
                item[4].abort()         # staged writer never flushed
                raise
            pending = (item[0], item[1], item[2], devs, item[4])
            written.append(item[1])
        if pending is not None:
            flush(pending)
            pending = None
    finally:
        if pending is not None:
            pending[4].abort()              # error path: no tmp debris
        if stager is not None:
            stager.close()
    return written


# --------------------------------------------------------- delta builds

def epoch_index_dir(outdir: str, epoch: int) -> str:
    """Where a delta rebuild for diff epoch ``epoch`` materializes: a
    sibling-free SUBDIR of the base index, so the epoch-swap machinery
    (worker promotion, the retime→rebuild hook) can find every epoch's
    index from the one path it already knows."""
    return os.path.join(outdir, f"epoch-e{int(epoch):06d}")


def diff_epoch_of(difffile: str) -> int | None:
    """Diff epoch encoded in a fused-diff file name
    (``fused-e<epoch>.diff``, the DiffEpochManager spool convention);
    None for names that don't carry one."""
    m = re.search(r"-e(\d+)\.diff$", os.path.basename(difffile or ""))
    return int(m.group(1)) if m else None


def delta_affected_targets(graph: Graph, changed_eids: np.ndarray,
                           w_old: np.ndarray, w_new: np.ndarray,
                           max_seeds: int | None = None,
                           seed_chunk: int = 512) -> np.ndarray | None:
    """Target rows whose first-move entries CAN change when the named
    edges change weight — the delta build's dirty set.

    The test is the classic tense-edge criterion run as one bounded
    reverse-relaxation pass: compute ``d_old(e → t)`` for every changed
    edge endpoint ``e`` (a batched relaxation on the TRANSPOSED graph —
    the reverse-reachability pass, B = endpoints, not N), then mark
    target ``t`` dirty iff some changed edge ``(u, v)`` satisfies
    ``min(w_old, w_new)(u,v) + d_old(v→t) <= d_old(u→t)``. For an
    INCREASE that condition (with ``w_old``) holds exactly when the
    edge lies on a co-optimal path into ``t`` — otherwise neither
    distances nor any argmin input within row ``t`` move; for a
    DECREASE it (with ``w_new``) holds exactly when the cheaper edge
    becomes tense — otherwise it still strictly loses everywhere. ``<=``
    (not ``<``) keeps argmin TIES dirty, which is what makes a spliced
    delta rebuild bit-identical to a from-scratch build. Unreachable
    ``d_old(v→t) = INF`` rows stay clean: weight changes never create
    reachability.

    Returns the sorted dirty target ids, or ``None`` when the changed
    edge set exceeds the ``max_seeds`` bound
    (``DOS_BUILD_DELTA_MAX_SEEDS``; <= 0 = unbounded) — the caller then
    degrades to a full rebuild, the conservative answer.
    """
    from ..ops.bellman_ford import dist_to_targets

    changed_eids = np.asarray(changed_eids, np.int64)
    if len(changed_eids) == 0:
        return np.zeros(0, np.int64)
    ends_all = np.unique(np.concatenate(
        [graph.src[changed_eids], graph.dst[changed_eids]]))
    if max_seeds is None:
        max_seeds = env_cast("DOS_BUILD_DELTA_MAX_SEEDS", 4096, int)
    if max_seeds > 0 and len(ends_all) > max_seeds:
        log.info("delta pass: %d changed-edge endpoints exceed the "
                 "DOS_BUILD_DELTA_MAX_SEEDS=%d bound; degrading to a "
                 "full rebuild", len(ends_all), max_seeds)
        return None
    # transposed graph under OLD weights: dist_to_targets(gT, e) gives
    # d_T(x -> e) = d_old(e -> x) for every node x in one [B, N] solve
    g_t = Graph(graph.xs, graph.ys, graph.dst, graph.src, w_old)
    dg_t = DeviceGraph.from_graph(g_t)
    minw = np.minimum(np.asarray(w_old, np.int64)[changed_eids],
                      np.asarray(w_new, np.int64)[changed_eids])
    inf64 = int(INF)
    dirty = np.zeros(graph.n, bool)
    per = max(seed_chunk // 2, 1)
    for i in range(0, len(changed_eids), per):
        eids = changed_eids[i:i + per]
        eu = graph.src[eids]
        ev = graph.dst[eids]
        ends = np.unique(np.concatenate([eu, ev]))
        # pad to the pow2 of the ACTUAL endpoint count (capped at the
        # chunk): a 10-edge hotspot must pay a 16-wide solve, not a
        # 512-wide one — the pass's cost tracks the delta's size
        csize = min(seed_chunk,
                    1 << (max(len(ends), 1) - 1).bit_length())
        pad = np.full(csize, -1, np.int32)
        pad[:len(ends)] = ends
        d = np.asarray(dist_to_targets(
            dg_t, jnp.asarray(pad))).astype(np.int64)   # [B, N]
        du = d[np.searchsorted(ends, eu)]
        dv = d[np.searchsorted(ends, ev)]
        tense = (dv < inf64) & (minw[i:i + per][:, None] + dv <= du)
        dirty |= tense.any(axis=0)
    return np.nonzero(dirty)[0].astype(np.int64)


def _compute_rows_batched(compute_with_count, tgts: np.ndarray,
                          chunk_rows: int) -> np.ndarray:
    """Solve fm rows for an arbitrary target list in chunk batches —
    the shared recompute unit of the delta paths. Full batches reuse
    the chunk's compiled shape; the final partial batch pads to its
    own pow2 (capped at the chunk) so a handful of dirty rows never
    pays a whole-chunk solve."""
    donate = env_flag("DOS_BUILD_DONATE", True)
    parts = []
    for i in range(0, len(tgts), chunk_rows):
        part = tgts[i:i + chunk_rows]
        csize = min(chunk_rows,
                    1 << (max(len(part), 1) - 1).bit_length())
        pad = np.full(csize, -1, np.int32)
        pad[:len(part)] = part
        d, cd = compute_with_count(pad)
        parts.append(fetch_fm(d, count_dev=cd,
                              donate=donate)[:len(part)])
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _delta_compute_ctx(ctx: dict | None, graph_new: Graph,
                       method: str, max_iters: int) -> dict:
    """Lazily resolved per-DELTA compute context: the build kernel
    choice, the device-resident graph, and the dispatch closure are
    identical across every shard of one delta, so an in-process
    multi-shard driver (``delta_build_index``) shares ONE DeviceGraph
    upload instead of re-uploading the CSR arrays per shard. ``ctx``
    is the shared mutable cache (``None`` = private, standalone
    callers); a delta where every block copies never populates it."""
    if ctx is None:
        ctx = {}
    if "compute" not in ctx:
        kind, structure = pick_build_kernel(graph_new, method)
        dg = DeviceGraph.from_graph(graph_new)
        ctx["kind"] = kind
        ctx["compute"] = _make_chunk_compute(dg, kind, structure,
                                             max_iters)
    return ctx


def delta_build_worker_shard(graph_new: Graph, dc: DistributionController,
                             wid: int, old_outdir: str, outdir: str,
                             dirty: np.ndarray | None,
                             old_blocks_meta: dict | None = None,
                             chunk: int = 0, max_iters: int = 0,
                             resume: bool = True, method: str = "auto",
                             epoch: int = 0,
                             compute_ctx: dict | None = None) -> dict:
    """One worker's shard of a DELTA rebuild: blocks with no dirty row
    are byte-copied from the old index (digest journaled, zero device
    work), dirty blocks recompute ONLY their dirty rows on the retimed
    graph and splice them into the old block's clean rows. ``dirty`` is
    the [N] bool mask from :func:`delta_affected_targets`; ``None`` (or
    a dirty fraction above ``DOS_BUILD_DELTA_MAX_FRAC``) degrades the
    whole shard to a pipelined full rebuild — whole-shard-dirty is the
    regime where splicing only adds overhead. Every block lands through
    the same atomic write + epoch-keyed ledger line as a full build, so
    a crash mid-delta resumes at block granularity and a stale-epoch
    journal never satisfies the resume check."""
    os.makedirs(outdir, exist_ok=True)
    owned = dc.owned(wid)
    bs = dc.block_size
    n_blocks = (len(owned) + bs - 1) // bs
    report = {"blocks": n_blocks, "rows_recomputed": 0,
              "blocks_skipped": 0, "blocks_resumed": 0,
              "degraded_full": False}
    dirty_owned = (np.ones(len(owned), bool) if dirty is None
                   else np.asarray(dirty, bool)[owned])
    max_frac = env_cast("DOS_BUILD_DELTA_MAX_FRAC", 0.75, float)
    if dirty is None or (len(owned)
                         and dirty_owned.mean() > max_frac):
        # the degraded full rebuild keeps the old index's block codec
        # (first recorded one — indexes are built under one knob), so
        # a compressed index's delta chain stays compressed even when
        # the splice does not pay
        codec_hint = next(
            (m.get("codec") for m in (old_blocks_meta or {}).values()
             if isinstance(m, dict) and m.get("codec")), "raw")
        written = build_worker_shard(graph_new, dc, wid, outdir,
                                     chunk=chunk, max_iters=max_iters,
                                     resume=resume, method=method,
                                     epoch=epoch, codec=codec_hint)
        report["degraded_full"] = True
        report["rows_recomputed"] = int(
            min(len(written) * bs, len(owned)))
        M_DELTA_ROWS.inc(report["rows_recomputed"])
        return report
    ledger = BuildLedger(outdir, wid)
    entries = ledger.entries() if resume else {}
    old_blocks_meta = old_blocks_meta or {}

    def crash_point() -> None:
        rule = faults.inject("crash-build", wid=wid)
        if rule is not None:
            if rule.mode == "exit":
                os._exit(faults.KILL_EXIT_CODE)
            raise RuntimeError("crash-build fault injected")

    # pass 1 — classify every block (resume / byte-copy / rebuild) and
    # collect the rebuild blocks' dirty targets, so pass 2 can solve
    # them in SHARD-WIDE chunk batches: per-block solves would shatter
    # the multi-row batching (and its compiled-shape reuse) that makes
    # the kernels fast — the same amortization the full build lives
    # on. Old rows are NOT retained here (only the verify status):
    # pass 2 re-reads each dirty block as it lands, bounding host
    # memory to the recompute batch plus ONE block instead of every
    # dirty block's copy at once.
    todo: list[tuple] = []        # (bid, fname, blk, bmask, old_ok)
    recompute_tgts: list[np.ndarray] = []
    for bid in range(n_blocks):
        fname = shard_block_name(wid, bid)
        if resume and _block_done(outdir, fname, entries, epoch):
            report["blocks_resumed"] += 1
            M_BLOCKS_RESUMED.inc()
            continue
        lo, hi = bid * bs, min((bid + 1) * bs, len(owned))
        blk = owned[lo:hi]
        bmask = dirty_owned[lo:hi].copy()
        old_path = os.path.join(old_outdir, fname)
        old_meta = old_blocks_meta.get(fname)
        if not bmask.any():
            todo.append((bid, fname, blk, None, False))  # byte copy
            continue
        status, reason = check_block(old_path, old_meta)
        old_ok = status in ("ok", "unverified")
        if not old_ok:
            if status != "missing":
                log.warning("delta rebuild of %s: old block is %s "
                            "(%s); recomputing every row", fname,
                            status, reason)
            bmask[:] = True          # no clean base to splice into
        todo.append((bid, fname, blk, bmask, old_ok))
        recompute_tgts.append(blk[bmask])

    rows_new = None
    if recompute_tgts:
        tgts_all = np.concatenate(recompute_tgts)
        compute_ctx = _delta_compute_ctx(compute_ctx, graph_new,
                                         method, max_iters)
        chunk_rows = build_chunk_rows(graph_new, chunk, len(owned),
                                      kind=compute_ctx["kind"])
        rows_new = _compute_rows_batched(compute_ctx["compute"],
                                         tgts_all, chunk_rows)

    # pass 2 — land blocks in bid order through the same atomic write +
    # epoch-keyed ledger discipline as a full build (crash-build fires
    # between flushes, so mid-delta kills resume at block granularity)
    off = 0
    for bid, fname, blk, bmask, old_ok in todo:
        old_path = os.path.join(old_outdir, fname)
        old_meta = old_blocks_meta.get(fname)
        # spliced/recomputed blocks keep the OLD block's codec — a
        # compressed index's delta chain stays compressed (byte copies
        # carry the container verbatim anyway)
        out_codec = (old_meta or {}).get("codec")
        if bmask is None:
            # clean block: byte copy, digest cross-checked against the
            # old manifest — a MISSING source (quarantined, swept) or a
            # torn one recomputes instead of aborting the shard or
            # propagating rot into the new epoch
            try:
                digest = atomic_copy_file(old_path,
                                          os.path.join(outdir, fname))
            except OSError as e:
                log.warning("delta copy of %s failed (%s); "
                            "recomputing", fname, e)
                digest = None
            if digest is None or (old_meta and old_meta.get("digest")
                                  and digest != old_meta["digest"]):
                if digest is not None:
                    log.warning("delta copy of %s does not match the "
                                "old manifest digest (%s != %s); "
                                "recomputing", fname, digest,
                                old_meta["digest"])
                arr = _delta_single_block(graph_new, blk, chunk,
                                          len(owned), method, max_iters,
                                          compute_ctx)
                n_new = len(blk)
            else:
                arr = np.load(os.path.join(outdir, fname),
                              mmap_mode="r")
                ledger.record(fname, digest, arr.shape,
                              str(arr.dtype), epoch=epoch,
                              codec=out_codec)
                report["blocks_skipped"] += 1
                M_DELTA_SKIPPED.inc()
                crash_point()
                continue
        else:
            n_new = int(bmask.sum())
            fresh = rows_new[off:off + n_new]
            off += n_new
            if not old_ok:
                arr = fresh          # bmask was forced all-dirty
            else:
                # old rows re-read HERE, one block at a time (pass 1
                # kept only the verify status) — bounded host memory
                rows_old, status, reason = load_verified_block(
                    old_path, old_meta)
                if rows_old is not None:
                    try:
                        # compressed old blocks inflate for the splice
                        rows_old = maybe_decode_rows(rows_old)
                    except ValueError as e:
                        rows_old, status, reason = (
                            None, "corrupt", f"undecodable: {e}")
                if rows_old is None:
                    # vanished/torn between passes (rare race): the
                    # batched fresh rows only cover bmask, so the
                    # whole block recomputes
                    log.warning("delta splice of %s: old block "
                                "became %s between passes (%s); "
                                "recomputing every row", fname,
                                status, reason)
                    arr = _delta_single_block(graph_new, blk, chunk,
                                              len(owned), method,
                                              max_iters, compute_ctx)
                    n_new = len(blk)
                else:
                    arr = np.asarray(rows_old).copy()
                    arr[bmask] = fresh
        enc = encode_block(arr, out_codec)
        if enc is not None:
            arr, out_codec = enc
        else:
            out_codec = None
        digest = atomic_save_npy(os.path.join(outdir, fname), arr)
        ledger.record(fname, digest, arr.shape, str(arr.dtype),
                      epoch=epoch, codec=out_codec)
        report["rows_recomputed"] += n_new
        M_DELTA_ROWS.inc(n_new)
        crash_point()
    return report


def _delta_single_block(graph_new: Graph, blk: np.ndarray, chunk: int,
                        n_owned: int, method: str, max_iters: int,
                        compute_ctx: dict | None = None) -> np.ndarray:
    """Recompute one whole block outside the shard-wide batch — the
    rare torn-copy fallback path of :func:`delta_build_worker_shard`
    (sharing the delta's compute context, so even this path never
    re-uploads the device graph)."""
    ctx = _delta_compute_ctx(compute_ctx, graph_new, method, max_iters)
    chunk_rows = build_chunk_rows(graph_new, chunk, n_owned,
                                  kind=ctx["kind"])
    return _compute_rows_batched(ctx["compute"], blk, chunk_rows)


def delta_build_index(graph: Graph, dc: DistributionController,
                      old_outdir: str, difffile: str,
                      epoch: int | None = None,
                      out_root: str | None = None, chunk: int = 0,
                      max_iters: int = 0, method: str = "auto",
                      resume: bool = True, workers=None) -> dict:
    """Delta rebuild: old index + a fused diff epoch → a NEW
    epoch-tagged index (``epoch_index_dir``) bit-identical to a
    from-scratch build on the retimed graph, recomputing only the rows
    the changed edges can actually affect.

    The changed edge set is ``w_new != w_old`` where ``w_old`` comes
    from the old manifest's recorded ``diff_file`` (absent = free flow
    — a plain build), so delta-on-delta chains compose. The affected
    rows come from :func:`delta_affected_targets`; untouched blocks
    byte-copy with their ledger/manifest digests reused. The resulting
    index carries ``diff_epoch``/``diff_file`` manifest keys (unknown
    to old readers — the codec contract) so the epoch-swap machinery
    can promote it under a running serve
    (``worker.engine.ShardEngine.promote_index``).
    """
    old_manifest = read_manifest(old_outdir)
    check_manifest_version(old_manifest, old_outdir)
    old_diff = old_manifest.get("diff_file", "-")
    try:
        w_old = graph.weights_with_diff(old_diff)
    except OSError as e:
        # the old index's fused diff was pruned from the spool (the
        # DiffEpochManager keep window outlives only keep_epochs
        # files): without it the changed-edge set is unknowable, so
        # the delta DEGRADES to a full rebuild on the retimed graph —
        # still a correct epoch index, never a failed chain link
        log.warning("old index %s records diff_file %s which is "
                    "unreadable (%s); delta degrades to a full "
                    "rebuild", old_outdir, old_diff, e)
        w_old = None
    w_new = graph.weights_with_diff(difffile)
    changed = (np.nonzero(w_new != w_old)[0] if w_old is not None
               else np.zeros(0, np.int64))
    if epoch is None:
        epoch = diff_epoch_of(difffile)
    if epoch is None:
        epoch = int(old_manifest.get("diff_epoch", 0)) + 1
    outdir = epoch_index_dir(out_root or old_outdir, int(epoch))
    graph_new = Graph(graph.xs, graph.ys, graph.src, graph.dst, w_new)
    if w_old is None:
        dirty = None                          # unknown delta: full
    elif len(changed) == 0:
        dirty = np.zeros(graph.n, bool)       # empty delta: copy all
    else:
        affected = delta_affected_targets(graph, changed, w_old, w_new)
        if affected is None:
            dirty = None                      # degrade to full
        else:
            dirty = np.zeros(graph.n, bool)
            dirty[affected] = True
    report: dict = {
        "epoch": int(epoch), "outdir": outdir,
        "changed_edges": int(len(changed)),
        "affected_rows": (int(graph.n) if dirty is None
                          else int(dirty.sum())),
        "rows_recomputed": 0, "blocks_skipped": 0,
        "blocks_resumed": 0, "degraded_full": False, "shards": 0,
    }
    # one compute context for the WHOLE delta: kernel choice and the
    # device-resident graph are shard-invariant, so the in-process
    # multi-shard loop uploads the CSR arrays once, not per shard
    ctx: dict = {}
    with obs_trace.span("cpd.delta_build", epoch=int(epoch),
                        changed=int(len(changed))):
        for wid in (range(dc.maxworker) if workers is None else workers):
            rep = delta_build_worker_shard(
                graph_new, dc, wid, old_outdir, outdir, dirty,
                old_blocks_meta=old_manifest.get("blocks", {}),
                chunk=chunk, max_iters=max_iters, resume=resume,
                method=method, epoch=int(epoch), compute_ctx=ctx)
            report["shards"] += 1
            report["rows_recomputed"] += rep["rows_recomputed"]
            report["blocks_skipped"] += rep["blocks_skipped"]
            report["blocks_resumed"] += rep["blocks_resumed"]
            report["degraded_full"] |= rep["degraded_full"]
        if workers is None and dc.replication > 1:
            # replica sets copy from the NEW primaries in the same dir
            for host in range(dc.maxworker):
                for r in range(1, dc.replication):
                    copy_replica_blocks(dc, (host - r) % dc.maxworker,
                                        r, outdir, resume=resume)
        if workers is None:
            write_index_manifest(
                outdir, dc,
                rows_per_worker=old_manifest.get("rows_per_worker"),
                extra={"diff_epoch": int(epoch),
                       "diff_file": os.path.abspath(difffile)})
    log.info("delta build epoch %d: %d changed edge(s) -> %d/%d rows "
             "recomputed, %d block(s) copied%s -> %s", epoch,
             report["changed_edges"], report["rows_recomputed"],
             graph.n, report["blocks_skipped"],
             " (degraded to full)" if report["degraded_full"] else "",
             outdir)
    return report


def _primary_codec(outdir: str, shard: int) -> str:
    """The codec shard ``shard``'s PRIMARY blocks were written with
    (ledger first, block sniff second, raw default) — what a replica
    RECOMPUTE must use so its digest can ever match the primary's in
    the anti-entropy cross-check."""
    for ent in BuildLedger(outdir, shard).entries().values():
        if ent.get("codec"):
            return str(ent["codec"])
    try:
        arr = np.load(os.path.join(outdir, shard_block_name(shard, 0)),
                      mmap_mode="r")
        if is_container(arr):
            return str(block_codec(arr))
    except (OSError, ValueError) as e:
        log.debug("primary codec sniff for shard %d failed (%s); "
                  "assuming raw", shard, e)
    return "raw"


def copy_replica_blocks(dc: DistributionController, shard: int,
                        replica: int, outdir: str,
                        resume: bool = True) -> list[str]:
    """Materialize shard ``shard``'s rank-``replica`` block set by
    copying digest-valid PRIMARY blocks — the cheap path when builder
    and primary share a filesystem (the kernels are deterministic, so
    the copy is exactly what a recompute would produce). Blocks whose
    primary is missing or unparsable are skipped (the caller recomputes
    them via :func:`build_worker_shard(..., replica=r)`). Copies go
    through the same atomic-write + ledger journal as built blocks, so
    resume/verify/heal treat them identically. Returns names written."""
    os.makedirs(outdir, exist_ok=True)
    owned = dc.n_owned(shard)
    bs = dc.block_size
    n_blocks = (owned + bs - 1) // bs
    ledger = BuildLedger(outdir, shard, replica)
    entries = ledger.entries() if resume else {}
    prim_ledger = BuildLedger(outdir, shard).entries()
    written = []
    for bid in range(n_blocks):
        fname = shard_block_name(shard, bid, replica)
        if resume and block_complete(outdir, fname, entries):
            continue
        prim = shard_block_name(shard, bid)
        prim_path = os.path.join(outdir, prim)
        prim_ent = prim_ledger.get(prim)
        rows, status, _reason = _verify_block(
            prim_path,
            {"digest": prim_ent["digest"]} if prim_ent else None,
            want_rows=True)
        if rows is None:
            continue        # no healthy primary: caller recomputes
        # a compressed primary copies verbatim — the replica ships
        # (and stores) the compressed container bytes
        digest = atomic_save_npy(os.path.join(outdir, fname),
                                 np.asarray(rows))
        ledger.record(fname, digest, rows.shape, str(rows.dtype),
                      codec=(block_codec(np.asarray(rows))
                             if is_container(rows) else None))
        M_REPLICA_COPIED.inc()
        written.append(fname)
    return written


def build_replica_shards(graph: Graph, dc: DistributionController,
                         host_wid: int, outdir: str, chunk: int = 0,
                         resume: bool = True,
                         method: str = "auto") -> dict[int, list[str]]:
    """Build every replica block set worker ``host_wid`` hosts (ranks
    1..R-1 of :meth:`~..parallel.partition.DistributionController
    .replica_shards`): copy from digest-valid primaries where possible,
    recompute the rest from the graph. No-op at R=1. Returns
    ``{shard: [files written]}``."""
    out: dict[int, list[str]] = {}
    for r in range(1, dc.replication):
        shard = (host_wid - r) % dc.maxworker
        copied = copy_replica_blocks(dc, shard, r, outdir, resume=resume)
        # recomputed replica blocks keep the PRIMARY's codec — a raw
        # recompute of a compressed primary would fail the anti-entropy
        # digest cross-check forever (quarantine/rebuild loop)
        computed = build_worker_shard(graph, dc, shard, outdir,
                                      chunk=chunk, resume=True,
                                      method=method, replica=r,
                                      codec=_primary_codec(outdir,
                                                           shard))
        out[shard] = sorted(set(copied) | set(computed))
        if copied or computed:
            log.info("worker %d: replica r%d of shard %d ready "
                     "(%d copied, %d computed)", host_wid, r, shard,
                     len(copied), len(computed))
    return out


def _block_meta_for(outdir: str, fname: str,
                    ledgers: dict[tuple, dict]) -> dict:
    """Digest/shape/dtype for one block file, cheapest source first:
    the worker's build ledger (digest already computed from the written
    bytes), else read the file once."""
    wid = int(fname.split("-")[1][1:])
    replica = block_file_replica(fname)
    key = (wid, replica)
    if key not in ledgers:
        ledgers[key] = BuildLedger(outdir, wid, replica).entries()
    ent = ledgers[key].get(fname)
    if ent is not None and "digest" in ent:
        meta = {"digest": ent["digest"], "shape": list(ent["shape"]),
                "dtype": ent["dtype"]}
        if ent.get("codec"):
            meta["codec"] = ent["codec"]
        return meta
    path = os.path.join(outdir, fname)
    arr = np.load(path, mmap_mode="r")
    meta = {"digest": digest_file(path), "shape": list(arr.shape),
            "dtype": str(arr.dtype)}
    # compressed containers are self-describing — an un-ledgered one
    # still gets its codec into the manifest
    if is_container(arr):
        meta["codec"] = block_codec(np.asarray(arr))
    return meta


def write_index_manifest(outdir: str, dc: DistributionController,
                         rows_per_worker: int | None = None,
                         workers=None, block_meta: dict | None = None,
                         extra: dict | None = None) -> dict:
    """Write ``index.json`` describing a per-block CPD index (the head
    runs this after all workers' builds finish). Written atomically.

    v2 manifests record per-block content digests, shapes, and dtypes
    under ``blocks`` (``digest_algo`` names the checksum), so every
    later load/verify can tell a valid block from a torn or rotted one.
    ``block_meta`` optionally supplies those entries (digests computed
    at write time); anything missing is harvested from the per-worker
    build ledgers, and only as a last resort read back from disk.

    ``workers``: optional subset of worker ids to enumerate — a PARTIAL
    index for single-worker serving (the analog of the reference's ``-w``
    filter): streamed/resident serving then answers only queries whose
    target those workers own; other workers' rows load as "stuck".

    ``extra``: additional manifest keys (the delta build's
    ``diff_epoch``/``diff_file`` tags) — unknown to older readers,
    which tolerate them per the codec contract; callers must not shadow
    the required partition keys.
    """
    files = []
    replica_files = []
    bs = dc.block_size
    for wid in (range(dc.maxworker) if workers is None else workers):
        n_owned = dc.n_owned(wid)
        for bid in range((n_owned + bs - 1) // bs):
            fname = shard_block_name(wid, bid)
            if not os.path.exists(os.path.join(outdir, fname)):
                raise FileNotFoundError(
                    f"index incomplete: missing {fname} "
                    f"(worker {wid} block {bid})")
            files.append(fname)
            for r in range(1, dc.replication):
                rname = shard_block_name(wid, bid, r)
                if not os.path.exists(os.path.join(outdir, rname)):
                    raise FileNotFoundError(
                        f"index incomplete: missing replica {rname} "
                        f"(shard {wid} block {bid} rank {r}, hosted by "
                        f"worker {(wid + r) % dc.maxworker})")
                replica_files.append(rname)
    ledgers: dict[tuple, dict] = {}
    blocks = {}
    for fname in files + replica_files:
        meta = (block_meta or {}).get(fname)
        blocks[fname] = meta if meta is not None else _block_meta_for(
            outdir, fname, ledgers)
    manifest = {
        "version": INDEX_VERSION,
        "digest_algo": "crc32",
        "nodenum": dc.nodenum,
        "maxworker": dc.maxworker,
        "partmethod": dc.partmethod,
        "partkey": (list(dc.partkey)
                    if isinstance(dc.partkey, (list, tuple)) else dc.partkey),
        "block_size": bs,
        "rows_per_worker": (rows_per_worker if rows_per_worker is not None
                            else max(dc.max_owned, 1)),
        "files": files,
        "blocks": blocks,
    }
    if dc.replication > 1:
        # replica keys ride the same schema version: unknown keys are
        # tolerated by every reader (the compat contract), and an R=1
        # index stays byte-identical to the pre-replication format
        manifest["replication"] = dc.replication
        manifest["replica_files"] = replica_files
    if extra:
        manifest.update(extra)
    atomic_write_json(os.path.join(outdir, "index.json"), manifest)
    return manifest


def validate_manifest(manifest: dict, dc: DistributionController,
                      outdir: str) -> None:
    """Check a loaded ``index.json`` against the serving controller (the
    reference keeps build and serve consistent by passing the same
    partmethod/partkey quadruple everywhere; we verify it).

    Schema compatibility is the wire codecs' contract: unknown keys are
    tolerated (a v1 index loads under v2 code, and a v2 index's digest
    keys are invisible to v1-era fields), and only a manifest whose
    version is NEWER than this code rejects — those may have changed
    the meaning of keys we would silently misread."""
    check_manifest_version(manifest, outdir)
    my_partkey = (list(dc.partkey)
                  if isinstance(dc.partkey, (list, tuple)) else dc.partkey)
    for key, mine in (("nodenum", dc.nodenum),
                      ("maxworker", dc.maxworker),
                      ("partmethod", dc.partmethod),
                      ("partkey", my_partkey),
                      ("block_size", dc.block_size)):
        if key not in manifest:
            raise ValueError(
                f"index {outdir} manifest is missing required key "
                f"{key!r}")
        if manifest[key] != mine:
            raise ValueError(
                f"index {outdir} was built with {key}={manifest[key]}, "
                f"controller has {mine}")
    # replication is NOT a hard cross-check: an R=1 index serves an
    # R>1 controller (replica sets just aren't on disk yet — failover
    # loads fall back to primaries) and vice versa; the key is only
    # meaningful to verify/anti-entropy passes, which read it directly.


def check_manifest_version(manifest: dict, outdir: str) -> None:
    """The version half of :func:`validate_manifest`, callable on its
    own by load paths that have no controller to cross-check (the
    engine's ``load_shard_rows``): a manifest NEWER than this code may
    have changed the meaning of keys we would silently misread — reject
    it outright instead of mis-verifying every block."""
    version = int(manifest.get("version", 1))
    if version > INDEX_VERSION:
        raise ValueError(
            f"index {outdir} has manifest schema v{version}; this build "
            f"reads up to v{INDEX_VERSION} — upgrade the serving code "
            "(unknown keys are tolerated, newer major versions are not)")


def _verify_block(path: str, meta: dict | None, want_rows: bool):
    """One block's verification against its manifest entry — the single
    implementation behind :func:`check_block` (verify-only: streamed
    digest + mmap'd header, no row materialization) and
    :func:`load_verified_block` (one file read: digest over the bytes
    in memory, then parse those same bytes). Returns
    ``(rows | None, status, reason)`` with status one of ``ok``
    (digest-verified), ``unverified`` (parses, but no digest to check —
    v1 manifest), ``missing``, ``corrupt``."""
    if not os.path.exists(path):
        return None, "missing", "file absent"
    need_digest = bool(meta and meta.get("digest"))
    try:
        if want_rows:
            with open(path, "rb") as f:
                data = f.read()
            got = digest_bytes(data) if need_digest else None
            arr = np.load(io.BytesIO(data))
        else:
            got = digest_file(path) if need_digest else None
            arr = np.load(path, mmap_mode="r")
        if need_digest and got != meta["digest"]:
            return None, "corrupt", (f"digest {got} != manifest "
                                     f"{meta['digest']}")
        if meta:
            if ("shape" in meta
                    and list(arr.shape) != list(meta["shape"])):
                return None, "corrupt", (
                    f"shape {list(arr.shape)} != manifest "
                    f"{list(meta['shape'])}")
            if "dtype" in meta and str(arr.dtype) != meta["dtype"]:
                return None, "corrupt", (f"dtype {arr.dtype} != "
                                         f"manifest {meta['dtype']}")
            if meta.get("codec"):
                # compressed block: the container header must parse
                # and name the manifest's codec — a payload that
                # digests clean but decodes to the wrong codec (or to
                # garbage) is corrupt, not servable
                got_codec = (block_codec(np.asarray(arr))
                             if is_container(arr) else None)
                if got_codec != meta["codec"]:
                    return None, "corrupt", (
                        f"codec {got_codec!r} != manifest "
                        f"{meta['codec']!r}")
    except Exception as e:  # noqa: BLE001 — torn header, short file, ...
        return None, "corrupt", f"unreadable: {type(e).__name__}: {e}"
    return (arr if want_rows else None,
            "ok" if need_digest else "unverified", "")


def check_block(path: str, meta: dict | None) -> tuple[str, str]:
    """Verify one block file WITHOUT materializing the rows (streamed
    digest, mmap'd header); returns ``(status, reason)``."""
    _, status, reason = _verify_block(path, meta, want_rows=False)
    return status, reason


def load_verified_block(path: str, meta: dict | None):
    """Load one block's rows with verification in a SINGLE file read;
    returns ``(rows | None, status, reason)`` — rows is None whenever
    status is ``missing``/``corrupt``."""
    return _verify_block(path, meta, want_rows=True)


def heal_block(outdir: str, manifest: dict | None, fname: str, wid: int,
               graph: Graph, dc: DistributionController,
               status: str = "corrupt", reason: str = "") -> np.ndarray:
    """The shared self-heal sequence of both load paths
    (``CPDOracle.load`` and the engine's ``load_shard_rows``):
    quarantine the bad block, rebuild it in place from the graph
    (``build_worker_shard`` with resume recomputes exactly the blocks
    whose ledger/digest check fails — here, only the quarantined one),
    reload, and refresh the manifest entry when the rebuilt digest
    differs from the recorded one — otherwise every later load would
    re-flag the healthy rebuild as corrupt and rebuild it again.
    Returns the rebuilt rows; raises ``ValueError`` when the rebuild
    itself cannot produce a loadable block."""
    path = os.path.join(outdir, fname)
    qpath = quarantine(path)
    replica = block_file_replica(fname)
    meta = (manifest or {}).get("blocks", {}).get(fname)
    log.warning("CPD block %s is %s (%s); %srebuilding from the graph",
                fname, status, reason,
                f"quarantined to {qpath}; " if qpath else "")
    with obs_trace.span("cpd.rebuild", file=fname, wid=wid,
                        replica=replica):
        if replica:
            # a replica heals from its primary when one is on disk
            # (digest-valid copy), recomputing only as a fallback
            copy_replica_blocks(dc, wid, replica, outdir)
        # the rebuild keeps the block's recorded codec so a healed
        # compressed index stays compressed (and vice versa) — the
        # manifest, not the process env, owns the block's format
        build_worker_shard(graph, dc, wid, outdir, replica=replica,
                           codec=(meta or {}).get("codec", "raw"))
    rows, _status2, reason2 = load_verified_block(path, None)
    if rows is None:
        raise ValueError(
            f"CPD block {fname} in {outdir} could not be rebuilt: "
            f"{reason2} (original fault: {reason})")
    M_BLOCKS_REBUILT.inc()
    new_digest = digest_file(path)
    if meta is not None and meta.get("digest") != new_digest:
        if meta.get("digest"):
            log.warning(
                "rebuilt %s has digest %s != manifest %s (different "
                "build kernel?); refreshing the manifest entry",
                fname, new_digest, meta["digest"])
        new_meta = {"digest": new_digest, "shape": list(rows.shape),
                    "dtype": str(rows.dtype)}
        if is_container(rows):
            new_meta["codec"] = block_codec(np.asarray(rows))
        manifest["blocks"][fname] = new_meta
        atomic_write_json(os.path.join(outdir, "index.json"), manifest)
    # callers serve rows, not containers
    return maybe_decode_rows(rows)


def read_manifest(outdir: str) -> dict:
    with open(os.path.join(outdir, "index.json")) as f:
        return json.load(f)


def verify_index(outdir: str, dc: DistributionController | None = None,
                 manifest: dict | None = None) -> dict:
    """Check-only integrity pass over a CPD index: every manifest block
    is digest/shape-verified in place (``make_cpds --verify``, and the
    bench's post-build gate). Returns a report dict::

        {"total": N, "ok": n, "unverified": [...],   # no digest (v1)
         "missing": [...], "corrupt": [{"file","reason"}, ...],
         "fatal": "..."}                              # manifest-level

    ``dc`` additionally cross-checks the partition quadruple. Mapped to
    exit codes by :func:`verify_exit_code` (0/3/4 clean/degraded/
    corrupt, the campaign driver's convention)."""
    report: dict = {"total": 0, "ok": 0, "unverified": [],
                    "missing": [], "corrupt": []}
    if manifest is None:
        try:
            manifest = read_manifest(outdir)
        except (OSError, ValueError) as e:
            report["fatal"] = f"no readable manifest in {outdir}: {e}"
            return report
    if dc is not None:
        try:
            validate_manifest(manifest, dc, outdir)
        except ValueError as e:
            report["fatal"] = str(e)
            return report
    blocks_meta = manifest.get("blocks", {})
    all_files = (list(manifest.get("files", []))
                 + list(manifest.get("replica_files", [])))
    report["total"] = len(all_files)
    for fname in all_files:
        with obs_trace.span("cpd.verify", file=fname):
            status, reason = check_block(os.path.join(outdir, fname),
                                         blocks_meta.get(fname))
        if status == "ok":
            M_BLOCKS_VERIFIED.inc()
            report["ok"] += 1
        elif status == "unverified":
            report["unverified"].append(fname)
        elif status == "missing":
            M_BLOCKS_CORRUPT.inc()
            report["missing"].append(fname)
        else:
            M_BLOCKS_CORRUPT.inc()
            report["corrupt"].append({"file": fname, "reason": reason})
    return report


def anti_entropy(outdir: str, dc: DistributionController,
                 graph: Graph | None = None,
                 manifest: dict | None = None, heal: bool = True) -> dict:
    """Replica anti-entropy pass: cross-check every replica block's
    crc32 digest against its PRIMARY's (the source of truth — primaries
    are verified by the normal load/verify paths), quarantining and
    healing divergent replicas in place.

    For each shard block and replica rank, the pass compares the
    on-disk replica digest to the primary's manifest/on-disk digest. A
    mismatch books ``replica_digest_mismatches_total`` and — with
    ``heal=True`` — quarantines the replica (``<file>.quarantined``)
    and re-materializes it from the primary (or from the graph when
    ``graph`` is given and the primary itself is unreadable), then
    refreshes the manifest entry. Divergence here means a torn/rotted
    replica OR a primary rebuilt under a different kernel since the
    replica was copied; either way the primary wins.

    Returns ``{"checked": n, "mismatched": [...], "healed": [...],
    "missing_primary": [...]}``. No-op (all zeros) at R=1.
    """
    report: dict = {"checked": 0, "mismatched": [], "healed": [],
                    "missing_primary": []}
    if dc.replication <= 1:
        return report
    if manifest is None:
        try:
            manifest = read_manifest(outdir)
        except (OSError, ValueError):
            manifest = None
    blocks_meta = (manifest or {}).get("blocks", {})
    manifest_dirty = False
    bs = dc.block_size
    for shard in range(dc.maxworker):
        n_blocks = (dc.n_owned(shard) + bs - 1) // bs
        for bid in range(n_blocks):
            prim = shard_block_name(shard, bid)
            prim_path = os.path.join(outdir, prim)
            prim_meta = blocks_meta.get(prim)
            prim_digest = (prim_meta or {}).get("digest")
            if prim_digest is None:
                try:
                    prim_digest = digest_file(prim_path)
                except OSError:
                    report["missing_primary"].append(prim)
                    continue      # nothing to cross-check against
            for r in range(1, dc.replication):
                rname = shard_block_name(shard, bid, r)
                rpath = os.path.join(outdir, rname)
                report["checked"] += 1
                try:
                    got = digest_file(rpath)
                except OSError:
                    got = None        # missing replica = divergent
                if got == prim_digest:
                    continue
                M_REPLICA_MISMATCH.inc()
                report["mismatched"].append(
                    {"file": rname, "digest": got,
                     "primary_digest": prim_digest})
                if not heal:
                    continue
                with obs_trace.span("cpd.anti_entropy", file=rname,
                                    shard=shard, replica=r):
                    quarantine(rpath)
                    copied = copy_replica_blocks(dc, shard, r, outdir)
                    if rname not in copied and graph is not None:
                        # recompute with the primary's codec (see
                        # build_replica_shards) so the healed digest
                        # can converge with the cross-check
                        build_worker_shard(
                            graph, dc, shard, outdir, replica=r,
                            codec=(prim_meta or {}).get(
                                "codec", _primary_codec(outdir,
                                                        shard)))
                rows, status, reason = load_verified_block(rpath, None)
                if rows is None:
                    log.error("anti-entropy could not heal %s: %s "
                              "(%s)", rname, status, reason)
                    continue
                report["healed"].append(rname)
                new_digest = digest_file(rpath)
                if (manifest is not None
                        and blocks_meta.get(rname, {}).get("digest")
                        != new_digest):
                    new_meta = {"digest": new_digest,
                                "shape": list(rows.shape),
                                "dtype": str(rows.dtype)}
                    if is_container(rows):
                        new_meta["codec"] = block_codec(
                            np.asarray(rows))
                    blocks_meta[rname] = new_meta
                    manifest_dirty = True
    if manifest_dirty:
        # one atomic manifest rewrite for the whole pass, not one per
        # healed block
        manifest["blocks"] = blocks_meta
        atomic_write_json(os.path.join(outdir, "index.json"), manifest)
    if report["mismatched"]:
        log.warning("anti-entropy: %d/%d replica block(s) diverged "
                    "from their primary (%d healed)",
                    len(report["mismatched"]), report["checked"],
                    len(report["healed"]))
    return report


def adopt_shard_blocks(graph: Graph, dc: DistributionController,
                       shard: int, outdir: str) -> dict:
    """Adopter catch-up for a membership ownership transfer
    (``parallel.membership``): make shard ``shard``'s PRIMARY block set
    servable on this filesystem — every block digest-verified against
    the manifest, anything missing/torn healed through the shared
    quarantine→copy→rebuild path (``heal_block``: a digest-valid
    replica set is copied before any recompute). Idempotent and
    crash-resumable for free: verification re-runs in O(read), and the
    heal path journals rebuilt blocks through the build ledger exactly
    like a normal build — a joining worker killed mid catch-up re-pays
    only the blocks that never landed.

    Returns ``{"shard", "blocks", "ok", "unverified", "healed": [...]}``;
    raises when a block can neither be verified nor healed (the
    migration must not commit over it)."""
    try:
        manifest = read_manifest(outdir)
    except (OSError, ValueError):
        manifest = None             # pre-manifest build: heal from graph
    if manifest is not None:
        check_manifest_version(manifest, outdir)
    blocks_meta = (manifest or {}).get("blocks", {})
    bs = dc.block_size
    n_blocks = (dc.n_owned(int(shard)) + bs - 1) // bs
    report: dict = {"shard": int(shard), "blocks": n_blocks, "ok": 0,
                    "unverified": 0, "healed": []}
    for bid in range(n_blocks):
        fname = shard_block_name(int(shard), bid)
        path = os.path.join(outdir, fname)
        with obs_trace.span("reshard.adopt", file=fname, shard=shard):
            status, reason = check_block(path, blocks_meta.get(fname))
            if status == "ok":
                report["ok"] += 1
            elif status == "unverified":
                report["unverified"] += 1
            else:
                M_BLOCKS_CORRUPT.inc()
                heal_block(outdir, manifest, fname, int(shard), graph,
                           dc, status=status, reason=reason)
                report["healed"].append(fname)
        M_BLOCKS_ADOPTED.inc()
    return report


def verify_exit_code(report: dict) -> int:
    """0 clean (every block ok or legacy-unverified), 3 degraded (some
    blocks bad), 4 corrupt (manifest unreadable/mismatched, or no block
    survived) — mirroring ``process_query``'s 0/3/4 convention."""
    if report.get("fatal"):
        return 4
    bad = len(report["missing"]) + len(report["corrupt"])
    if bad == 0:
        return 0
    good = report["ok"] + len(report["unverified"])
    return 3 if good > 0 else 4


class CPDOracle:
    def __init__(self, graph: Graph, controller: DistributionController,
                 mesh=None):
        self.graph = graph
        self.dc = controller
        self.mesh = mesh if mesh is not None else make_mesh(
            n_workers=min(controller.maxworker, len(jax.devices())))
        if self.mesh.shape[WORKER_AXIS] != controller.maxworker:
            raise ValueError(
                f"mesh worker axis {self.mesh.shape[WORKER_AXIS]} != "
                f"maxworker {controller.maxworker}; partmethod=tpu requires "
                "one mesh shard per worker")
        self.dg = DeviceGraph.from_graph(graph)
        self.targets_wr = pad_targets(controller)
        self.fm = None     # int8 [W, R, N], sharded on worker axis
        self.dists = None  # optional int32 [W, R, N] (build(store_dists=True))
        #: per-diff PADDED device weight buffers for the mat family
        #: (keyed by the caller's w_key, LRU-bounded like the engine's
        #: weight cache): a serving frontend answers many mat rows
        #: under one diff, and re-padding + re-uploading [M+1] ints per
        #: row would dominate the collective it feeds
        self._mat_weights: dict = {}

    # ------------------------------------------------------------- build
    def build(self, chunk: int = 0, max_iters: int = 0,
              store_dists: bool = False,
              method: str = "auto") -> "CPDOracle":
        """Precompute all first-move rows, sharded over the mesh.

        ``store_dists=True`` also keeps the converged distance table (4x
        the fm memory) enabling :meth:`query_dist` — free-flow answers by
        one gather instead of a path walk. Distances are free-flow only
        and are not persisted by :meth:`save` (they are a pure derivative
        of the graph; rebuild to get them back).

        ``method``: ``"sweep"`` forces the fast-sweeping build, ``"shift"``
        the gather-free shift relaxation, ``"frontier"`` the
        delta-stepping queue, ``"ell"``/``"ellsplit"`` the (split)
        padded-ELL gather; ``"auto"`` resolves per
        :func:`pick_build_kernel`.
        """
        kind, structure = pick_build_kernel(self.graph, method)
        if store_dists:
            self.fm, self.dists = build_fm_sharded(
                self.dg, self.targets_wr, self.mesh, chunk=chunk,
                max_iters=max_iters, with_dists=True,
                kernel=(kind, structure))
        else:
            self.fm = build_fm_sharded(self.dg, self.targets_wr, self.mesh,
                                       chunk=chunk, max_iters=max_iters,
                                       kernel=(kind, structure))
        return self

    # ------------------------------------------------------- persistence
    def save(self, outdir: str, codec: str | None = None) -> None:
        """Write the CPD index: one .npy per (worker, block) + manifest.

        ``codec``: persist blocks compressed (``models.resident``
        containers; None resolves ``DOS_CPD_RESIDENT`` — the ``raw``
        default keeps the legacy byte-identical layout). Per-block
        degrade to raw when not viable; the manifest's ``blocks``
        entries record the codec that applied (unknown-key tolerant).

        Multi-controller safe: with >1 JAX process each WORKER's slice
        is allgathered separately (its shards live on non-addressable
        devices) and only process 0 writes — host memory peaks at 1/W of
        the table (at the README's NY scale: 8.7 GB instead of 70 GB per
        controller), and concurrent controllers never race on the shared
        index directory."""
        if self.fm is None:
            raise RuntimeError("build() or load() before save()")
        codec_req = resident_choice() if codec is None else codec
        multi = jax.process_count() > 1
        if multi:
            from ..parallel.multihost import is_primary
            primary = is_primary()
        else:
            primary = True
        if primary:
            os.makedirs(outdir, exist_ok=True)
        bs = self.dc.block_size
        block_meta: dict[str, dict] = {}
        for wid in range(self.dc.maxworker):
            n_owned = self.dc.n_owned(wid)
            # ONE fetch per worker: bounded host memory (1/W of the
            # table) without a transfer round trip per block. Every
            # process participates
            # in the gather (collective); only the primary writes.
            rows_w = _host(self.fm[wid, :n_owned])
            if primary:
                for b0 in range(0, n_owned, bs):
                    fname = shard_block_name(wid, b0 // bs)
                    arr = np.ascontiguousarray(
                        rows_w[b0:min(b0 + bs, n_owned)])
                    enc = encode_block(arr, codec_req)
                    blk_codec = None
                    if enc is not None:
                        arr, blk_codec = enc
                    digest = atomic_save_npy(
                        os.path.join(outdir, fname), arr)
                    block_meta[fname] = {"digest": digest,
                                         "shape": list(arr.shape),
                                         "dtype": str(arr.dtype)}
                    if blk_codec is not None:
                        block_meta[fname]["codec"] = blk_codec
            del rows_w
        if primary:
            write_index_manifest(
                outdir, self.dc,
                rows_per_worker=int(self.targets_wr.shape[1]),
                block_meta=block_meta)

    def load(self, outdir: str, heal: bool = True) -> "CPDOracle":
        """Load a saved index onto the mesh, validating partition
        consistency (the reference keeps build and serve consistent by
        passing the same partmethod/partkey quadruple everywhere; we
        verify it) AND per-block content: every block is digest/shape
        checked as it loads (v2 manifests), so a torn write or bit-rot
        fails here with a per-block diagnostic instead of poisoning
        queries.

        ``heal=True`` (default): a missing/corrupt block is quarantined
        (``<file>.quarantined``) and rebuilt in place from the graph —
        the oracle always has it resident — then re-verified; the
        manifest entry is refreshed if the rebuilt digest differs (e.g.
        the original index predates the current kernel selection).
        ``heal=False`` raises on the first bad block instead."""
        manifest = read_manifest(outdir)
        validate_manifest(manifest, self.dc, outdir)
        blocks_meta = manifest.get("blocks", {})
        w = self.dc.maxworker
        r = self.targets_wr.shape[1]
        fm = np.full((w, r, self.graph.n), -1, np.int8)
        bs = self.dc.block_size
        for fname in manifest["files"]:
            stem = fname[:-len(".npy")]
            _, wpart, bpart = stem.split("-")
            wid, bid = int(wpart[1:]), int(bpart[1:])
            path = os.path.join(outdir, fname)
            meta = blocks_meta.get(fname)
            with obs_trace.span("cpd.verify", file=fname):
                rows, status, reason = load_verified_block(path, meta)
            if rows is None:
                M_BLOCKS_CORRUPT.inc()
                if not heal:
                    raise ValueError(
                        f"CPD block {fname} in {outdir} is {status}: "
                        f"{reason}")
                rows = heal_block(outdir, manifest, fname, wid,
                                  self.graph, self.dc,
                                  status=status, reason=reason)
            elif status == "ok":
                # only digest-checked blocks count as verified; v1
                # (digest-less) blocks load fine but stay unverified
                M_BLOCKS_VERIFIED.inc()
            # compressed containers inflate here: the mesh oracle is
            # raw-resident (its [W, R, N] tensor shards over workers);
            # compressed RESIDENCY is the ShardEngine's serving path
            rows = maybe_decode_rows(rows)
            fm[wid, bid * bs: bid * bs + len(rows)] = rows
        self.fm = jax.device_put(fm, worker_sharding(self.mesh, rank=3))
        return self

    # ------------------------------------------------------------- query
    def _length_estimate(self, queries: np.ndarray) -> np.ndarray:
        return length_estimate(self.graph, queries[:, 0], queries[:, 1])

    def _plan(self, queries: np.ndarray, active_worker: int = -1):
        """``(wids, active, qmax)`` of a routed call: each query's owner,
        the queries the call answers, and the lanes each (data slot,
        worker) walks: the largest group over the data axis, bucketed
        to the next power of two so that calls of similar size share
        one compiled program."""
        wids = self.dc.worker_of(queries[:, 1])
        active = np.ones(len(queries), bool) if active_worker == -1 \
            else wids == active_worker
        sizes = np.bincount(wids[active], minlength=self.dc.maxworker)
        d = self.mesh.shape[DATA_AXIS]
        qmax = max(int(np.ceil(sizes.max() / d)) if active.any() else 0, 1)
        return wids, active, 1 << (qmax - 1).bit_length()

    def routed_lanes(self, queries: np.ndarray,
                     active_worker: int = -1) -> int:
        """Lanes per (data slot, worker) that a call over ``queries``
        walks (:meth:`route`'s padded ``Q``)."""
        return self._plan(np.asarray(queries, np.int64), active_worker)[2]

    def route(self, queries: np.ndarray, active_worker: int = -1,
              plan=None):
        """Pack (s, t) queries into mesh-shaped [D, W, Q] arrays.

        Returns ``(t_rows, s, t, valid, scatter)`` where ``scatter`` maps
        each input query to its (d, w, q) slot for unpacking results.
        ``plan``: :meth:`_plan` of the same call, when the caller has it.

        Within each worker group, queries are ordered by expected walk
        length (:meth:`_length_estimate`) so the kernel's bucketed
        while_loops (``ops.table_search`` ``n_buckets``) each halt at
        their own bucket's max length instead of the batch max.
        """
        queries = np.asarray(queries, np.int64)
        nq = len(queries)
        d = self.mesh.shape[DATA_AXIS]
        w = self.dc.maxworker
        wids, active, qmax = (self._plan(queries, active_worker)
                              if plan is None else plan)
        rows = self.dc.owned_index_of(queries[:, 1])
        # round-robin each worker's queries over the data axis (vectorized):
        # the k-th query of worker w goes to data slot k % d, column k // d
        slot_d = np.zeros(nq, np.int64)
        slot_q = np.zeros(nq, np.int64)
        est = self._length_estimate(queries)
        # sort by (worker, est): worker-major grouping as before; est
        # ordering within a group makes slot_q ascend with walk length
        idxs = np.nonzero(active)[0][np.lexsort(
            (est[active], wids[active]))]
        wids_sorted = wids[idxs]
        group_sizes = np.bincount(wids_sorted, minlength=w)
        starts = np.concatenate([[0], np.cumsum(group_sizes)[:-1]])
        seq = np.arange(len(idxs)) - np.repeat(starts, group_sizes)
        slot_d[idxs] = seq % d
        slot_q[idxs] = seq // d

        s_arr = np.zeros((d, w, qmax), np.int32)
        t_arr = np.zeros((d, w, qmax), np.int32)
        r_arr = np.zeros((d, w, qmax), np.int32)
        valid = np.zeros((d, w, qmax), bool)
        s_arr[slot_d[active], wids[active], slot_q[active]] = queries[active, 0]
        t_arr[slot_d[active], wids[active], slot_q[active]] = queries[active, 1]
        r_arr[slot_d[active], wids[active], slot_q[active]] = rows[active]
        valid[slot_d[active], wids[active], slot_q[active]] = True
        scatter = (active, slot_d, wids, slot_q)
        return r_arr, s_arr, t_arr, valid, scatter

    @staticmethod
    def _unroute(scatter, nq: int, arrays, lead_flags):
        """Scatter routed ``[D, W, Q, ...]`` device results back to input
        query order (the inverse of :meth:`route`'s packing). Arrays
        flagged in ``lead_flags`` carry a leading per-diff axis
        (``[Dd, D, W, Q]``) that is preserved. Bool arrays come back
        bool; everything else int64. Inactive queries stay zero, the
        reference's ``-w`` filter semantics (``process_query.py:59``)."""
        active, sd, sw, sq = scatter
        outs = []
        for a, lead in zip(arrays, lead_flags):
            a = np.asarray(a)
            dt = bool if a.dtype == np.bool_ else np.int64
            if lead:
                out = np.zeros((a.shape[0], nq) + a.shape[4:], dt)
                out[:, active] = a[:, sd[active], sw[active], sq[active]]
            else:
                out = np.zeros((nq,) + a.shape[3:], dt)
                out[active] = a[sd[active], sw[active], sq[active]]
            outs.append(out)
        return outs

    def _staged(self, queries: np.ndarray, active_worker: int, walk,
                lead_flags):
        """One routed call in its three stages, each a span tagged
        ``pairs=`` and ``lanes=`` and a histogram observation:
        ``cpd.route`` (host), ``cpd.walk`` (``walk(routed)``: upload,
        launch, every shard's answers ready), ``cpd.fetch`` (answers
        to the host in input order)."""
        plan = self._plan(queries, active_worker)
        pairs = int(plan[1].sum())
        lanes = self.mesh.shape[DATA_AXIS] * self.dc.maxworker * plan[2]
        with _stage("cpd.route", M_ROUTE, pairs, lanes):
            r_arr, s_arr, t_arr, valid, scatter = self.route(
                queries, active_worker, plan)
        with _stage("cpd.walk", M_WALK, pairs, lanes):
            outs = jax.block_until_ready(walk((r_arr, s_arr, t_arr,
                                               valid)))
        with _stage("cpd.fetch", M_FETCH, pairs, lanes):
            res = tuple(self._unroute(scatter, len(queries),
                                      _host_tree(outs), lead_flags))
        M_PAIRS.inc(pairs)
        M_LANES.inc(lanes)
        return res

    def _device_weights(self, w_query: np.ndarray | None):
        """Padded query-time weights on the device: free flow is
        resident already; a diffed round pays one upload."""
        return self.dg.w_pad if w_query is None else jnp.asarray(
            self.graph.padded_weights(w_query), jnp.int32)

    def _walk(self, w_query, k_moves: int, max_steps: int):
        """``routed -> (cost, plen, finished)`` on the devices."""
        def walk(routed):
            return query_sharded(
                self.dg, self.fm, *routed, self._device_weights(w_query),
                self.mesh, k_moves=k_moves, max_steps=max_steps,
                kernel=self._walk_kernel(routed[0].shape))
        return walk

    def _walk_multi(self, w_diffs, max_steps: int):
        """``routed -> (cost [D, ...], plen, finished)``, one fused walk
        costed under every diff of ``w_diffs``."""
        def walk(routed):
            return query_multi_sharded(
                self.dg, self.fm, *routed,
                self.graph.padded_weights_multi(w_diffs), self.mesh,
                max_steps=max_steps)
        return walk

    def query(self, queries: np.ndarray, w_query: np.ndarray | None = None,
              k_moves: int = -1, active_worker: int = -1,
              max_steps: int = 0):
        """Answer queries in input order.

        ``w_query``: perturbed edge weights (file order), None = free flow.
        Returns ``(cost, plen, finished)`` int64/bool arrays [Q]; queries
        outside ``active_worker`` (when set) come back cost 0 / unfinished,
        like the reference's ``-w`` filter drops them
        (``process_query.py:59``).
        """
        if self.fm is None:
            raise RuntimeError("build() or load() before query()")
        return self._staged(np.asarray(queries, np.int64), active_worker,
                            self._walk(w_query, k_moves, max_steps),
                            (False, False, False))

    def warm(self, lanes, w_query: np.ndarray | None = None,
             w_diffs: list | None = None, k_moves: int = -1,
             max_steps: int = 0) -> None:
        """Compile (or load from the compilation cache) the walk that
        :meth:`query` (or, with ``w_diffs``, :meth:`query_multi`) runs
        at each routed width in ``lanes`` (:meth:`routed_lanes`), on
        batches of padding only, so that no later call of those widths
        compiles."""
        if self.fm is None:
            raise RuntimeError("build() or load() before warm()")
        walk = (self._walk(w_query, k_moves, max_steps) if w_diffs is None
                else self._walk_multi(w_diffs, max_steps))
        shape = (self.mesh.shape[DATA_AXIS], self.dc.maxworker)
        for q in sorted(set(lanes)):
            idx = np.zeros(shape + (q,), np.int32)
            jax.block_until_ready(walk(
                (idx, idx, idx, np.zeros(shape + (q,), bool))))

    def _walk_kernel(self, routed_shape) -> str:
        """Resolve ``DOS_WALK_KERNEL`` for one routed batch. The policy
        lives in ``ops.pallas_walk.choose_walk_kernel``; this method
        only supplies the shard-local batch size."""
        from ..ops.pallas_walk import choose_walk_kernel

        dgrid, _, qmax = routed_shape
        # the shard-local flat batch: [D/|data|, 1, Q] reshaped to -1
        q_local = max(dgrid // max(self.mesh.shape[DATA_AXIS], 1), 1) \
            * qmax
        return choose_walk_kernel(
            self.dg.n, self.dg.k, int(self.dg.w_pad.shape[0]) - 1,
            q_local)

    def query_multi(self, queries: np.ndarray,
                    w_diffs: list[np.ndarray | None],
                    active_worker: int = -1, max_steps: int = 0):
        """Answer queries under D congestion diffs in ONE fused walk.

        The reference campaign runs one round per diff file over the
        same scenario (``process_query.py:178``), re-walking every query
        each round. Trajectories are diff-independent (moves follow the
        free-flow table; diffs only change cost accumulation), so the
        fused kernel walks once and accumulates every diff's costs —
        ~2D/3 fewer gathers than D sequential rounds
        (:func:`~..ops.table_search.table_search_multi`).

        ``w_diffs``: list of per-diff edge-weight arrays (file order);
        ``None`` entries mean free flow. Returns ``(cost [D, Q],
        plen [Q], finished [Q])`` in input query order.
        """
        if self.fm is None:
            raise RuntimeError("build() or load() before query_multi()")
        if not w_diffs:
            raise ValueError("w_diffs must name at least one round")
        return self._staged(np.asarray(queries, np.int64), active_worker,
                            self._walk_multi(w_diffs, max_steps),
                            (True, False, False))

    def query_mat(self, s: int, targets,
                  w_query: np.ndarray | None = None,
                  w_key: str | None = None):
        """One ``mat`` family row — one source, K targets — with the
        JOIN ON MESH (``parallel.sharded.query_mat_sharded``): each
        shard walks the targets it owns and the dense ``[K]`` answer
        row assembles by a ``psum`` collective over the mesh axes,
        replacing the serving frontend's head-side fan-out/join (one
        future per target through queue + batcher + dispatcher).

        ``w_key``: a stable identity for ``w_query`` (the diff file
        path) — given one, the padded device weight buffer caches
        across rows (LRU, same bound discipline as the engine's
        per-diff cache), so serving many rows under one diff pays one
        upload, not one per row.

        Returns ``(cost [K] int64, finished [K] bool)`` in target
        order; an out-of-range target comes back unfinished with cost
        0 (the router cannot place it) rather than raising — the
        family layer encodes unanswered targets as ``-1`` either way.
        """
        if self.fm is None:
            raise RuntimeError("build() or load() before query_mat()")
        targets = np.asarray(targets, np.int64).reshape(-1)
        k = len(targets)
        ok = (targets >= 0) & (targets < self.graph.n)
        cost = np.zeros(k, np.int64)
        fin = np.zeros(k, bool)
        if not ok.any() or not (0 <= int(s) < self.graph.n):
            return cost, fin
        tgts = targets[ok]
        queries = np.stack(
            [np.full(len(tgts), int(s), np.int64), tgts], axis=1)
        r_arr, s_arr, t_arr, valid, scatter = self.route(queries)
        # each routed slot's position in the OUTPUT row: the on-mesh
        # scatter-add writes answers straight into target order, so
        # the host does no unroute at all
        active, sd, sw, sq = scatter
        slots = np.full(r_arr.shape, -1, np.int32)
        slots[sd, sw, sq] = np.arange(len(tgts), dtype=np.int32)
        w_pad = self._mat_w_pad(w_query, w_key)
        # the compiled row width pads to the next power of two: k is
        # CLIENT-controlled (one `mat` sentence per width), and an
        # un-padded width would compile-and-cache one program per
        # distinct k forever — the same stable-shape rule as route's
        # qmax and the engine's qpad. Pad slots never receive a
        # scatter, so the host just trims the row.
        k_pad = 1 << (len(tgts) - 1).bit_length()
        t0 = time.perf_counter()
        row_c, row_f = _host_tree(query_mat_sharded(
            self.dg, self.fm, r_arr, s_arr, t_arr, valid, slots,
            w_pad, self.mesh, k_out=k_pad))
        M_MESH_COLLECTIVE.observe(time.perf_counter() - t0)
        cost[ok] = np.asarray(row_c, np.int64)[:len(tgts)]
        fin[ok] = np.asarray(row_f, bool)[:len(tgts)]
        return cost, fin

    def _mat_w_pad(self, w_query, w_key):
        """The padded device weights one mat row walks under — cached
        per ``w_key`` (LRU, engine-style bound) so repeated rows under
        one diff re-use the uploaded buffer."""
        if w_query is None:
            return self.dg.w_pad
        if w_key is not None and w_key in self._mat_weights:
            return self._mat_weights[w_key]
        w_pad = jnp.asarray(self.graph.padded_weights(w_query),
                            jnp.int32)
        if w_key is not None:
            self._mat_weights[w_key] = w_pad
            while len(self._mat_weights) > 4:
                self._mat_weights.pop(next(iter(self._mat_weights)))
        return w_pad

    # ------------------------------------------------- prepared tables
    def table_memory_bytes(self) -> int:
        """Device bytes the prepared tables will occupy: int32 cost +
        sign-packed plen (int16 when N < 2^15) per (worker, row, node)."""
        from ..ops.pointer_doubling import plen_dtype

        w, r = self.targets_wr.shape
        per_entry = 4 + jnp.dtype(plen_dtype(self.graph.n)).itemsize
        return w * r * self.graph.n * per_entry

    @property
    def TABLE_BUDGET(self) -> int:
        """Per-device budget for prepared tables (bytes). Read lazily so
        DOS_TABLE_BUDGET_GB works as a runtime knob; malformed values
        fall back to the default (8 GB — conservative v5e headroom next
        to the resident fm + dists) instead of crashing."""
        gb = env_cast("DOS_TABLE_BUDGET_GB", 8.0, float)
        return int((gb if gb > 0 else 8.0) * 1e9)

    def prepare_weights(self, w_query: np.ndarray | None = None,
                        max_len: int = 0, chunk: int = 2048):
        """Pointer-doubling: precompute cost + packed plen for EVERY
        (source, owned-target) pair under ``w_query`` in O(log L) sweeps
        (``ops.pointer_doubling``). After this, :meth:`query_table`
        answers any query on these weights with one gather — the
        amortization path for huge campaigns, including congestion-diffed
        rounds where :meth:`query_dist` does not apply.

        **Measured trade (BENCH_r04 captures, 9216-node shard, v5e):**
        prepare ~19 s, lookups ~320-520k q/s vs the ~200-310k q/s
        diffed walk → break-even ~9-34M queries per diff round (the
        bench recomputes ``table_breakeven_queries`` from each run's
        own timings; it divides by the small walk-vs-lookup gap, hence
        the band — every point is the 10M-query-campaign regime).
        :meth:`prepare_weights_multi` divides the per-diff break-even
        by ~D. Memory: 6-8 bytes/entry = 6-8x the fm shard; calls
        whose tables exceed the per-device budget
        (``DOS_TABLE_BUDGET_GB``, default 8) raise with the math instead
        of faulting mid-campaign.

        ``chunk`` bounds the per-device rows doubled at once (several
        [rows, N] int32 live arrays per sweep; oversized batches fault).

        Returns an opaque tables handle to pass to :meth:`query_table`.
        """
        if self.fm is None:
            raise RuntimeError("build() or load() before prepare_weights()")
        need = self.table_memory_bytes()
        # tables shard over the WORKER axis only (build_tables_sharded
        # out_specs) — they are REPLICATED across the data axis, so the
        # per-device share divides by W, not by total device count
        n_w = max(self.mesh.shape[WORKER_AXIS], 1)
        budget = self.TABLE_BUDGET
        if need / n_w > budget:
            w, r = self.targets_wr.shape
            raise ValueError(
                f"prepared tables need {need / 1e9:.1f} GB "
                f"({w}x{r}x{self.graph.n} entries x "
                f"{need // (w * r * self.graph.n)} B, sharded over {n_w} "
                f"worker shard(s) = {need / n_w / 1e9:.1f} GB/device) — "
                f"over the {budget / 1e9:.1f} GB/device budget "
                "(DOS_TABLE_BUDGET_GB). At this scale serve via the walk "
                "or StreamedCPDOracle instead; the table trade only pays "
                "past ~10M queries per diff round anyway (measured "
                "break-even band, bench table_breakeven_queries).")
        w_pad = (self.dg.w_pad if w_query is None
                 else jnp.asarray(self.graph.padded_weights(w_query),
                                  jnp.int32))
        return self._chunked_tables(
            lambda fm_, tw_: build_tables_sharded(
                self.dg, fm_, tw_, w_pad, self.mesh, max_len=max_len),
            chunk)

    def _chunked_tables(self, build_one, chunk: int):
        """Run a sharded table builder over equal padded row-chunks of
        the target axis (one compiled program regardless of R) and trim
        the concatenated result — the shared scaffolding of
        :meth:`prepare_weights` and :meth:`prepare_weights_multi`."""
        r = self.targets_wr.shape[1]
        if chunk <= 0 or chunk >= r:
            return build_one(self.fm, self.targets_wr)
        pad = (-r) % chunk
        tw = self.targets_wr
        fm = self.fm
        if pad:
            tw = np.concatenate(
                [tw, np.full((tw.shape[0], pad), -1, tw.dtype)], axis=1)
            fm = jnp.concatenate(
                [fm, jnp.full((fm.shape[0], pad, fm.shape[2]), -1,
                              fm.dtype)], axis=1)
        parts = [build_one(fm[:, i:i + chunk], tw[:, i:i + chunk])
                 for i in range(0, tw.shape[1], chunk)]
        cat = lambda xs: jnp.concatenate(xs, axis=1)[:, :r]  # noqa: E731
        c, p = zip(*parts)
        return cat(c), cat(p)

    def query_table(self, tables, queries: np.ndarray,
                    active_worker: int = -1):
        """Answer queries from :meth:`prepare_weights` tables.

        Returns ``(cost, plen, finished)`` — identical to :meth:`query`
        on the same weights (tests pin this), at gather speed.
        """
        r_arr, s_arr, t_arr, valid, scatter = self.route(
            queries, active_worker)
        outs = _host_tree(query_tables_sharded(
            tables, r_arr, s_arr, valid, self.mesh))
        return tuple(self._unroute(scatter, len(queries), outs,
                                   (False, False, False)))

    def prepare_weights_multi(self, w_diffs: list[np.ndarray | None],
                              max_len: int = 0, chunk: int = 1024):
        """Fused pointer-doubling tables for D diffs at once.

        The doubling recursion is shared across diffs (free-flow
        successor function), so D diff rounds' cost tables cost ~ONE
        prepare's gather traffic
        (:func:`~..ops.pointer_doubling.doubled_tables_multi`) — the
        amortization regime of a multi-diff bulk campaign. Memory:
        ``4D + 2-4`` bytes per (row, node) entry, budget-gated like
        :meth:`prepare_weights`. ``chunk`` defaults lower than the
        single-diff path because each sweep's live working set widens
        by the D cost planes.

        Returns a tables handle for :meth:`query_table_multi`.
        """
        if self.fm is None:
            raise RuntimeError(
                "build() or load() before prepare_weights_multi()")
        if not w_diffs:
            raise ValueError("w_diffs must name at least one round")
        from ..ops.pointer_doubling import plen_dtype

        d = len(w_diffs)
        w, r = self.targets_wr.shape
        per_entry = 4 * d + jnp.dtype(plen_dtype(self.graph.n)).itemsize
        need = w * r * self.graph.n * per_entry
        n_w = max(self.mesh.shape[WORKER_AXIS], 1)
        budget = self.TABLE_BUDGET
        if need / n_w > budget:
            raise ValueError(
                f"fused tables for {d} diffs need {need / 1e9:.1f} GB "
                f"({per_entry} B/entry over {n_w} worker shard(s) = "
                f"{need / n_w / 1e9:.1f} GB/device) — over the "
                f"{budget / 1e9:.1f} GB/device budget "
                "(DOS_TABLE_BUDGET_GB). Prepare fewer diffs per call or "
                "serve via the fused walk (query_multi) instead.")
        w_pads = self.graph.padded_weights_multi(w_diffs)
        return self._chunked_tables(
            lambda fm_, tw_: build_tables_multi_sharded(
                self.dg, fm_, tw_, w_pads, self.mesh, max_len=max_len),
            chunk)

    def query_table_multi(self, tables, queries: np.ndarray,
                          active_worker: int = -1):
        """Answer queries from :meth:`prepare_weights_multi` tables:
        one ``[D]``-wide gather per query. Returns ``(cost [D, Q],
        plen [Q], finished [Q])`` — row d identical to
        :meth:`query_table` on diff d's tables (tests pin this)."""
        r_arr, s_arr, t_arr, valid, scatter = self.route(
            queries, active_worker)
        outs = _host_tree(query_tables_multi_sharded(
            tables, r_arr, s_arr, valid, self.mesh))
        return tuple(self._unroute(scatter, len(queries), outs,
                                   (True, False, False)))

    def query_paths(self, queries: np.ndarray, k: int,
                    active_worker: int = -1):
        """Materialize each query's first ``k`` path nodes (the
        reference's ``--k-moves`` extraction, reference ``args.py:31-36``).

        Returns ``(nodes, moves)``: int64 ``[Q, k+1]`` — row q starts at
        ``s``, the last node repeats once the path ends — and the number
        of real moves taken (≤ k). Queries outside ``active_worker`` get
        all-zero rows, matching :meth:`query`'s filter semantics.
        """
        if self.fm is None:
            raise RuntimeError("build() or load() before query_paths()")
        if k <= 0:
            raise ValueError("k must be positive")
        r_arr, s_arr, t_arr, valid, scatter = self.route(
            queries, active_worker)
        outs = _host_tree(query_paths_sharded(
            self.dg, self.fm, r_arr, s_arr, t_arr, self.mesh, k=k))
        return tuple(self._unroute(scatter, len(queries), outs,
                                   (False, False)))

    def query_dist(self, queries: np.ndarray, active_worker: int = -1):
        """Free-flow fast path: answer d(s → t) by one sharded gather.

        Requires ``build(store_dists=True)``. Returns ``(cost, finished)``
        — no ``plen`` (no path is materialized; that is the point:
        distance-only answers need no extraction, SURVEY.md §5). Costs on
        a diffed graph still need :meth:`query`.
        """
        if self.dists is None:
            raise RuntimeError(
                "distance table not resident; build(store_dists=True)")
        r_arr, s_arr, t_arr, valid, scatter = self.route(
            queries, active_worker)
        cost = _host(query_dist_sharded(self.dists, r_arr, s_arr,
                                             self.mesh))
        nq = len(queries)
        active, sd, sw, sq = scatter
        out_c = np.zeros(nq, np.int64)
        out_f = np.zeros(nq, bool)
        got = cost[sd[active], sw[active], sq[active]]
        fin = got < int(INF)
        out_c[active] = np.where(fin, got, 0)
        out_f[active] = fin
        return out_c, out_f
