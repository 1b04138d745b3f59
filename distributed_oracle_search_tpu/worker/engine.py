"""Per-shard query engine: the worker-resident compute path.

Role parity with the reference's resident ``fifo_auto`` process
(SURVEY.md §2.2 C3): load the graph, the congestion diff, and THIS worker's
CPD shard; then answer query batches for targets this shard owns. The
reference answers each query in a C++ loop over OpenMP threads; here the
whole batch is one XLA call — a vmapped first-move gather walk
(``ops.table_search``) on whatever single device this worker process owns
(TPU chip or CPU).

Runtime knobs honored per batch (reference ``process_query.py:149-160``):
``k_moves`` (move budget), ``itrs`` (repeat count; last result wins),
``no_cache`` (drop the per-diff weight cache). ``time`` (ns budget)
truncates INSIDE a batch like the reference's engine (reference
``args.py:30-57``): the length-sorted batch runs in fixed-size chunks
with the deadline checked between chunks, so an expired budget returns
partial ``finished`` counts (cheapest queries answered first; the first
chunk always runs so a minimal answer exists). Batches at or below one
chunk stay all-or-nothing — a single XLA call cannot stop mid-flight.
``threads``/``thread_alloc`` are accepted for wire parity but are no-ops
under XLA (SPMD inside one device replaces OpenMP, SURVEY.md §2.3).
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time
from collections import OrderedDict

import numpy as np

from ..data.formats import read_diff
from ..data.graph import Graph
from ..obs import device as obs_device
from ..obs import metrics as obs_metrics
from ..obs import quantiles as obs_quantiles
from ..obs import trace as obs_trace
from ..parallel.partition import DistributionController
from ..testing import faults
from ..transport.wire import RuntimeConfig, StatsRow
from ..utils.env import env_cast
from ..utils.locks import OrderedLock
from ..utils.log import get_logger, set_worker_id

log = get_logger(__name__)

# declared at import so a snapshot shows the engine's phase histograms
# even before the first batch (obs/__init__.py maps these to the wire
# stats fields t_receive/t_astar/t_search)
M_RECEIVE = obs_metrics.histogram(
    "worker_receive_seconds", "batch prep incl. weights (t_receive)")
M_WEIGHTS = obs_metrics.histogram(
    "worker_weights_load_seconds", "diff read + device weight upload")
M_SEARCH = obs_metrics.histogram(
    "worker_search_seconds",
    "steady-state search call (t_astar): walk launch until the device "
    "finished; the answers' fetch to the host comes after it "
    "(worker_fetch_seconds)")
M_FETCH = obs_metrics.histogram(
    "worker_fetch_seconds",
    "table-search answers to the host in request order after the "
    "walk: device slices, transfers, unsort, fan-out (outside t_astar "
    "and t_search)")
M_DEVICE_GAP = obs_metrics.histogram(
    "worker_device_gap_seconds",
    "table-search batches after an engine's first: host time from the "
    "previous batch's answers on the host to this batch's walk launch")
M_JIT = obs_metrics.histogram(
    "worker_jit_compile_seconds",
    "first call at a new (alg, shape, knobs) key — XLA compile + run, "
    "split out so steady-state latency stays clean")
M_BATCHES = obs_metrics.counter("worker_batches_total")
M_QUERIES = obs_metrics.counter("worker_queries_total")
M_DUPS = obs_metrics.counter(
    "worker_duplicate_queries_total",
    "queries answered from another identical (s, t) pair in the same "
    "batch — the kernel only runs each distinct pair once")
M_WALK_PALLAS = obs_metrics.counter(
    "walk_pallas_batches_total",
    "table-search batches answered by the Pallas-fused walk kernel "
    "(DOS_WALK_KERNEL selection, ops.pallas_walk)")
M_WALK_XLA = obs_metrics.counter(
    "walk_xla_batches_total",
    "table-search batches answered by the XLA reference walk")
M_MESH_DEVICES = obs_metrics.gauge(
    "mesh_devices",
    "devices in this worker's local lane mesh (DOS_MESH_DEVICES "
    "resolution; 1 = the legacy single-device engine)")
M_MESH_WALK = obs_metrics.counter(
    "mesh_walk_batches_total",
    "table-search batches split across the worker's mesh lanes "
    "(per-device bucket subsets under shard_map, bit-identical unsort)")
M_WALK_COMPRESSED = obs_metrics.counter(
    "walk_compressed_batches_total",
    "table-search batches answered from a compressed-resident CPD "
    "shard (DOS_CPD_RESIDENT: pack4 decompress-on-tile in the Pallas "
    "kernel, or the XLA run-start decode feeding either kernel)")


def load_shard_rows(outdir: str, wid: int, dc=None, graph=None,
                    heal: bool = True, replica: int = 0) -> np.ndarray:
    """Load one worker's CPD rows from the block files the builder wrote
    (``cpd-w<wid>-b<bid>.npy``; the index manifest is optional so a shard
    can serve before the whole cluster's build completes).

    When the manifest is present its per-block digests are verified as
    the rows load; a corrupt/torn block is quarantined and — when the
    caller supplies ``graph`` and ``dc`` (``ShardEngine`` does) —
    rebuilt in place, else the load fails with the per-block diagnostic
    instead of serving garbage answers.

    ``replica``: load shard ``wid``'s rank-``replica`` REPLICA block set
    (``cpd-w<wid>-r<r>-b<bid>.npy``) — the failover copy a non-primary
    host serves from. When no replica blocks exist but the primary set
    shares this filesystem (the common shared-nfs deployment), the load
    falls back to the primary files: the rows are identical by
    construction, and a failover must not die on a missing copy of data
    that is sitting right there."""
    from ..models.cpd import (
        M_BLOCKS_CORRUPT, M_BLOCKS_VERIFIED, check_manifest_version,
        heal_block, load_verified_block, read_manifest, shard_block_name,
    )
    from ..models.resident import maybe_decode_rows

    manifest: dict | None = None
    try:
        manifest = read_manifest(outdir)
    except (OSError, ValueError):
        pass                       # pre-manifest partial build: no digests
    if manifest is not None:
        # same schema gate as CPDOracle.load: a NEWER manifest's digest
        # entries must not be misread into mass quarantine/rebuild
        check_manifest_version(manifest, outdir)
    blocks_meta = (manifest or {}).get("blocks", {})
    # name prefix up to the block id: primary names must NOT match
    # replica entries of the same shard (and vice versa)
    prefix = shard_block_name(wid, 0, replica)[:-len("00000.npy")]
    pat = os.path.join(outdir, f"{prefix}*.npy")
    files = sorted(glob.glob(pat),
                   key=lambda p: int(re.search(r"-b(\d+)\.npy$", p).group(1)))
    # the manifest knows blocks the glob cannot see (deleted on disk)
    manifested = sorted(
        (os.path.join(outdir, f) for f in blocks_meta
         if f.startswith(prefix)),
        key=lambda p: int(re.search(r"-b(\d+)\.npy$", p).group(1)))
    files = manifested if manifested else files
    if not files and replica:
        log.warning("no rank-%d replica blocks for shard %d in %s; "
                    "falling back to the primary block set (same rows, "
                    "shared filesystem)", replica, wid, outdir)
        return load_shard_rows(outdir, wid, dc=dc, graph=graph,
                               heal=heal)
    if not files:
        raise FileNotFoundError(f"no CPD blocks for worker {wid} in {outdir}")
    parts = []
    for path in files:
        fname = os.path.basename(path)
        with obs_trace.span("cpd.verify", file=fname, wid=wid):
            rows, status, reason = load_verified_block(
                path, blocks_meta.get(fname))
        if rows is None:
            M_BLOCKS_CORRUPT.inc()
            if not heal or graph is None or dc is None:
                raise ValueError(
                    f"CPD block {fname} in {outdir} is {status}: {reason}"
                    + ("" if heal else " (healing disabled)")
                    + ("" if graph is not None and dc is not None
                       else " — no graph/controller to rebuild from; "
                            "load degraded"))
            rows = heal_block(outdir, manifest, fname, wid, graph, dc,
                              status=status, reason=reason)
        elif status == "ok":
            # only digest-checked blocks count as verified (same rule
            # as CPDOracle.load)
            M_BLOCKS_VERIFIED.inc()
        # compressed containers (models.resident) inflate to dense rows
        # here; whether the RESIDENT table re-compresses is the
        # caller's policy (ShardEngine._make_resident)
        parts.append(maybe_decode_rows(rows))
    return np.concatenate(parts, axis=0)


class ShardEngine:
    def __init__(self, graph: Graph, dc: DistributionController, wid: int,
                 outdir: str, alg: str = "table-search",
                 shard: int | None = None, replica: int | None = None,
                 mesh=None):
        from ..ops import DeviceGraph
        from ..parallel.mesh import LANE_AXIS, make_worker_mesh

        if alg not in ("table-search", "astar"):
            raise ValueError(f"unknown algorithm {alg!r}")
        self.alg = alg
        self.graph = graph
        self.dc = dc
        self.wid = wid
        #: worker-local lane mesh (``DOS_MESH_DEVICES``; an explicit
        #: ``mesh=`` ctor arg wins): the engine drives EVERY lane —
        #: walk batches split into per-device bucket subsets, the fm
        #: table replicated across lanes. ``None`` = the legacy
        #: single-device engine, byte-identical behavior.
        self.mesh = mesh if mesh is not None else make_worker_mesh()
        self.n_lanes = (self.mesh.shape[LANE_AXIS]
                        if self.mesh is not None else 1)
        M_MESH_DEVICES.set(self.n_lanes)
        #: base index directory the rows loaded from — where epoch-
        #: tagged delta-rebuilt indexes (``models.cpd.epoch_index_dir``)
        #: are discovered for background promotion
        self.outdir = outdir
        #: diff epoch of the PROMOTED first-move table (0 = none yet);
        #: bumped by :meth:`promote_index` when a delta-rebuilt epoch
        #: index lands. The base table stays resident: batch dispatch
        #: is epoch-GATED (:meth:`_fm_for`), so only batches naming the
        #: promoted epoch's fused diff walk the new table. The gate
        #: state itself lives in ``_fm_promoted`` as ONE ``(epoch,
        #: table)`` reference (atomic publish under the GIL);
        #: ``index_epoch`` mirrors the epoch for observers
        self.index_epoch = 0
        self._fm_promoted: tuple | None = None
        self._promote_lock = OrderedLock("worker.ShardEngine.promote")
        #: the SHARD whose rows this engine answers — ``wid`` itself for
        #: a primary engine, another shard when this worker serves a
        #: replica (failover/hedge target). The rows load from the
        #: matching replica block set.
        self.shard = wid if shard is None else int(shard)
        #: which block set serves the rows: the rank within the shard's
        #: replica chain, derived from the controller unless the caller
        #: pins it (a membership-migration adopter serves the PRIMARY
        #: set of a shard whose chain it has not joined yet)
        if replica is not None:
            self.replica = int(replica)
        else:
            self.replica = (dc.replica_rank(self.shard, wid)
                            if self.shard != wid else 0)
        #: REPLICA LANE: with a lane mesh, replica rank r pins to mesh
        #: lane ``r % L`` — each hosted replica serves from its OWN
        #: device, so an R>1 deployment on one host gives the breaker/
        #: hedge/failover paths a real second compute target instead of
        #: R engines time-slicing one chip (what let the TPU backend's
        #: R=1 pin lift, ``cli.process_query``). The primary (rank 0)
        #: keeps the whole mesh and lane-splits its batches instead.
        self._lane_device = None
        if self.mesh is not None and self.replica:
            self._lane_device = list(self.mesh.devices.flat)[
                self.replica % self.n_lanes]
        #: device-batch rows per A* chunk; the deadline is checked
        #: between chunks (first chunk always runs)
        self.astar_chunk = 1024
        #: resident-codec bookkeeping (statusz / compressed bench):
        #: what DOS_CPD_RESIDENT actually resolved to for THIS shard
        #: and the device bytes it occupies ("raw"/0 for astar engines)
        self.resident_codec = "raw"
        self.resident_bytes = 0
        if alg == "table-search":  # astar needs no first-move shard
            rows = load_shard_rows(
                outdir, self.shard, dc=dc, graph=graph,
                replica=self.replica)
            if faults.inject("corrupt-resident", self.shard) is not None:
                # flip row 0 AFTER the digest-verified load: in-memory
                # rot no manifest check can see — only the scrubber's
                # dense-row compare (integrity.scrub) catches it
                rows = np.array(rows, np.int8, copy=True)
                rows[0, :] = np.where(rows[0, :] <= 0, 1, 0)
            self.fm = self._make_resident(rows)
            owned = dc.owned(self.shard)
            if len(owned) != self.fm.shape[0]:
                raise ValueError(
                    f"shard w{self.shard}: {self.fm.shape[0]} CPD rows "
                    f"but controller owns {len(owned)} nodes — "
                    "partition mismatch")
        else:
            self.fm = None
        dg = DeviceGraph.from_graph(graph)
        if self._lane_device is not None or self._lane_split:
            # graph arrays follow the fm placement: pinned to the
            # replica's lane, or replicated across the lanes the
            # primary's shard_map walks read from
            dg = DeviceGraph(*(self._place(a) for a in dg))
        self.dg = dg
        #: per-diff device weight buffers, LRU-bounded: the live-traffic
        #: plane swaps fused diffs every few seconds, and an unbounded
        #: cache would pin one HBM weights array per epoch forever. The
        #: bound is >= 2 by construction — the DOUBLE BUFFER: when an
        #: epoch swap lands, in-flight batches still pinned to the old
        #: fused file finish on its resident buffer while new batches
        #: warm the new one (raw host-side astar entries share the
        #: budget; a re-upload after eviction is a read+transfer, never
        #: a correctness event)
        self._weight_cache: OrderedDict[object, object] = OrderedDict()
        self._weight_keep = max(
            2, env_cast("DOS_TRAFFIC_WEIGHT_EPOCHS", 4, int))
        #: (alg, qpad, knobs) keys whose program has already run once —
        #: the first call at a new key pays XLA compilation and is
        #: recorded to ``worker_jit_compile_seconds`` instead of the
        #: steady-state ``worker_search_seconds`` histogram
        self._jit_seen: set[tuple] = set()
        #: when the previous table-search batch's answers reached the
        #: host (``worker_device_gap_seconds`` runs from it)
        self._t_answered: float | None = None
        #: device-resident graph arrays for the batched A* serving path
        #: (in-ELL, coords, per-diff padded weights) — uploaded once, not
        #: per request (ops.batched_astar ctx contract)
        self._astar_ctx: dict = {}
        #: path prefixes of the most recent extract batch (see answer())
        self.last_paths: tuple[np.ndarray, np.ndarray] | None = None

    # ------------------------------------------------------------- mesh
    @property
    def _lane_split(self) -> bool:
        """Whether this engine splits its walk batches over mesh lanes:
        the PRIMARY engine of a mesh-driving worker does; replica
        engines pin to their own lane device instead; astar keeps the
        single-device batched kernel (its chunked deadline semantics
        are host-driven)."""
        return (self.mesh is not None and not self.replica
                and self.alg == "table-search")

    def _place(self, arr):
        """Device placement under the worker mesh: replica engines pin
        to their lane's device, the lane-splitting primary replicates
        across lanes (the shard's rows must be visible to every lane —
        any query's target row can be any row), and without a mesh this
        is the plain default-device upload."""
        import jax
        import jax.numpy as jnp
        from ..parallel.mesh import replicated

        if self._lane_device is not None:
            return jax.device_put(np.asarray(arr), self._lane_device)
        if self._lane_split:
            return jax.device_put(np.asarray(arr),
                                  replicated(self.mesh))
        return jnp.asarray(arr)

    def _make_resident(self, rows) -> object:
        """Materialize the resident first-move table under the
        ``DOS_CPD_RESIDENT`` policy (``models.resident``): the placed
        raw array (byte-identical legacy) or a :class:`CompressedFM`
        whose pack4/rle arrays live compressed in device memory and
        inflate per batch at the point of use. Placement (replica
        lane / mesh-replicated) is the same as the raw table's."""
        from ..models.resident import make_resident

        fm, codec = make_resident(rows, place=self._place)
        self.resident_codec = codec
        self.resident_bytes = int(fm.nbytes)
        return fm

    # ---------------------------------------------------------- promotion
    def _fm_for(self, difffile: str):
        """The table a batch walks: the promoted epoch table ONLY when
        the batch names the promoted epoch's fused diff file
        (``fused-e<N>.diff``), the base table otherwise. This gate is
        what keeps promotion safe under mixed traffic — an in-flight
        batch pinned to an older epoch (or a free-flow campaign batch)
        must keep its old-regime routes bit-identical, never pick up
        new-regime moves priced under its own weights. The published
        ``(epoch, table)`` pair is read ONCE — promotion swaps it as a
        single reference, so a concurrent promote can never tear the
        gate into comparing one epoch against another epoch's table."""
        promoted = self._fm_promoted        # one read: (epoch, table)
        if promoted is not None:
            from ..models.cpd import diff_epoch_of

            if diff_epoch_of(difffile) == promoted[0]:
                return promoted[1]
        return self.fm

    def promote_index(self, new_outdir: str, epoch: int) -> bool:
        """Make a delta-rebuilt epoch-tagged index servable under a
        running serve: load this shard's rows from ``new_outdir``
        (digest-verified like any load) and publish them as the
        PROMOTED table. Dispatch is epoch-gated (:meth:`_fm_for`): a
        batch naming that epoch's fused diff now gets OPTIMAL routes
        for the new regime instead of old-regime paths re-priced by
        query-time diff application, while every other batch — older
        epochs in flight, free flow — keeps walking the base table
        unchanged. Returns False (nothing changes) when the load fails:
        promotion is an optimization, never a serve outage.

        NOTE for result-caching frontends: promotion CHANGES the
        correct answer for the promoted epoch (re-priced old paths →
        optimal new paths), so cache entries keyed to that diff epoch
        that were computed before the promotion must be invalidated —
        the serving cache's epoch-scoped flush is the tool."""
        if self.alg != "table-search":
            return False
        try:
            # heal=False, no graph: the self-heal path would rebuild a
            # corrupt epoch-index block from THIS engine's free-flow
            # graph — wrong-regime rows persisted with valid digests
            # and then served as the epoch's optimal table. A bad
            # epoch index simply does not promote; the base table is
            # always a correct fallback.
            rows = load_shard_rows(new_outdir, self.shard, dc=self.dc,
                                   heal=False, replica=self.replica)
        except (OSError, ValueError, FileNotFoundError) as e:
            log.error("worker %d: cannot promote epoch %d index from "
                      "%s: %s (keeping epoch %d)", self.wid, epoch,
                      new_outdir, e, self.index_epoch)
            return False
        if rows.shape[0] != self.fm.shape[0]:
            log.error("worker %d: epoch %d index has %d rows, resident "
                      "table %d — partition mismatch, not promoting",
                      self.wid, epoch, rows.shape[0], self.fm.shape[0])
            return False
        # single-reference publish under the promote lock, MONOTONE in
        # epoch: two async promotions finishing out of order must not
        # let the older one overwrite the newer table (the gate would
        # then refuse current-epoch traffic until the next swap). The
        # lock covers only the check+assign; _fm_for reads stay
        # lock-free on the one published reference.
        with self._promote_lock:
            cur = self._fm_promoted
            if cur is not None and int(epoch) <= cur[0]:
                log.warning("worker %d: not promoting epoch %d over "
                            "already-promoted epoch %d", self.wid,
                            epoch, cur[0])
                return False
            # the promoted table rides the same resident-codec policy
            # as the base one (compressed residency applies per table)
            self._fm_promoted = (int(epoch), self._make_resident(rows))
            self.index_epoch = int(epoch)
        log.info("worker %d: promoted shard %d to diff-epoch %d index "
                 "(%s)", self.wid, self.shard, epoch, new_outdir)
        return True

    def promote_index_async(self, new_outdir: str,
                            epoch: int) -> threading.Thread:
        """Background :meth:`promote_index` — the epoch-swap hook's
        form: the load happens off the serve path and the ``fm`` rebind
        is a single reference swap. Returns the (daemon) thread so
        callers that care about completion can join it."""
        def _run():
            try:
                self.promote_index(new_outdir, epoch)
            except Exception as e:  # noqa: BLE001 — a failed promotion
                # keeps the old table; the serve path must never die
                log.error("worker %d: async promotion to epoch %d "
                          "failed: %s", self.wid, epoch, e)

        t = threading.Thread(
            target=_run, name=f"dos-build-promote-w{self.wid}",
            daemon=True)
        t.start()
        return t

    # ------------------------------------------------------------ weights
    def _weights_for(self, difffile: str, no_cache: bool):
        if difffile in self._weight_cache and not no_cache:
            self._weight_cache.move_to_end(difffile)
            return self._weight_cache[difffile]
        if difffile == "-":
            w_pad = self.dg.w_pad
        else:
            w = self.graph.weights_with_diff(read_diff(difffile))
            # placement follows the fm table (lane-replicated / pinned)
            w_pad = self._place(np.asarray(
                self.graph.padded_weights(w), np.int32))
        if no_cache:
            self._weight_cache.clear()
        else:
            self._weight_cache[difffile] = w_pad
            self._trim_weight_cache()
        return w_pad

    def _trim_weight_cache(self) -> None:
        while len(self._weight_cache) > self._weight_keep:
            self._weight_cache.popitem(last=False)

    # -------------------------------------------------------------- batch
    def answer(self, queries: np.ndarray, config: RuntimeConfig,
               difffile: str = "-") -> tuple[np.ndarray, np.ndarray,
                                             np.ndarray, StatsRow]:
        """Answer a batch; returns (cost, plen, finished, stats).

        With ``config.extract`` and ``k_moves > 0`` the extracted path
        prefixes land on ``self.last_paths`` as ``(nodes [Q, k+1],
        moves [Q])`` — the server materializes them into the batch's
        ``.paths`` file (wire extension, see ``transport.wire``).
        """
        import jax
        import jax.numpy as jnp
        from ..models.resident import M_DECOMPRESS, CompressedFM
        from ..ops.pallas_walk import choose_walk_kernel, pallas_walk_batch
        from ..ops.table_search import extract_paths, table_search_batch

        set_worker_id(self.wid)
        t0 = time.perf_counter()
        with obs_trace.span("worker.prep"):
            self.last_paths = None
            queries = np.asarray(queries, np.int64).reshape(-1, 2)
            # routing invariant FIRST — before any shard-local row lookup,
            # so a misrouted query fails with this diagnostic instead of an
            # opaque index/shape error out of owned_index_of or the kernel
            if len(queries):
                owner = self.dc.worker_of(queries[:, 1])
                if (owner != self.shard).any():
                    bad = int((owner != self.shard).sum())
                    raise ValueError(
                        f"shard w{self.shard} received {bad} queries for "
                        "other workers — routing invariant violated")
            with obs_trace.span("worker.weights", wid=self.wid,
                                difffile=difffile):
                w_pad = self._weights_for(difffile, config.no_cache)
            # the first-move table is epoch-gated per batch: the promoted
            # delta index serves ONLY the epoch whose fused diff the batch
            # names; everything else keeps the base table (see _fm_for)
            fm_tbl = self._fm_for(difffile)
            M_WEIGHTS.observe(time.perf_counter() - t0)
            nq = len(queries)
            if nq == 0:
                if config.extract and config.k_moves > 0:
                    self.last_paths = (
                        np.zeros((0, config.k_moves + 1), np.int64),
                        np.zeros(0, np.int64))
                elif config.sig_k > 0:
                    self.last_paths = (
                        np.zeros((0, config.sig_k + 1), np.int64),
                        np.zeros(0, np.int64))
                return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                        np.zeros(0, bool), StatsRow())
            # dedupe identical (s, t) pairs: skewed/online traffic repeats
            # pairs, and the kernel only needs each distinct pair once —
            # answers fan back out through `inverse`, the same machinery
            # as the length-sort's `unsort` below. The A* path keeps the raw
            # batch (its per-query deadline semantics and priority-queue
            # counters measure the work actually done).
            if self.alg == "astar":
                uniq, inverse = queries, None
            else:
                uniq, inverse = np.unique(queries, axis=0,
                                          return_inverse=True)
                inverse = inverse.reshape(-1)
                if len(uniq) < nq:
                    M_DUPS.inc(nq - len(uniq))
            nu = len(uniq)
            # order by expected walk length so the kernel's bucketed
            # while_loops exit early (the same trick as CPDOracle.route;
            # answers are unsorted back before returning)
            from ..models.cpd import length_estimate

            order = np.argsort(
                length_estimate(self.graph, uniq[:, 0], uniq[:, 1]),
                kind="stable")
            unsort = np.argsort(order)
            qsorted = uniq[order]
            # pad to the next power of two: stable shapes, no recompiles as the
            # per-worker batch size shifts between campaigns. A lane-mesh
            # engine pads at least to the lane count so EVERY batch splits
            # evenly over the mesh (the extra rows are valid=False lanes)
            qpad = 1 << (nu - 1).bit_length()
            if self._lane_split:
                qpad = max(qpad, self.n_lanes)
            s = np.zeros(qpad, np.int32)
            t = np.zeros(qpad, np.int32)
            valid = np.zeros(qpad, bool)
            s[:nu] = qsorted[:, 0]
            t[:nu] = qsorted[:, 1]
            valid[:nu] = True
            rows = np.zeros(qpad, np.int32)
            rows[:nu] = self.dc.owned_index_of(qsorted[:, 1])

        t1 = time.perf_counter()
        M_RECEIVE.observe(t1 - t0)
        # the compile/steady split keys on the COMPILED PROGRAM's shape:
        # the chunked paths (astar always; table-search under a time
        # budget once the batch exceeds one chunk) reuse a chunk-wide
        # program across batch sizes, so a bigger qpad alone is not a
        # recompile — except with --extract, whose extraction program
        # does compile at the full qpad (kept in the key, conservative)
        extracting = config.extract and config.k_moves > 0
        if self.alg == "astar":
            # the astar program depends only on its chunk shape: hscale/
            # fscale are traced scalars and k_moves/extract never reach
            # it (reference args.py:28), so they stay out of the key
            jit_key = ("astar", min(qpad, self.astar_chunk))
        else:
            if (config.time and qpad > self.astar_chunk
                    and not extracting and config.sig_k <= 0):
                # sig extraction (like extract) runs at the full qpad,
                # so its compile must stay attributable to this key
                shape_key = self.astar_chunk
            else:
                shape_key = qpad
            # compressed residency (DOS_CPD_RESIDENT, models.resident):
            # a pack4 shard feeds the Pallas kernel's decompress-on-
            # tile loader DIRECTLY (packed rows stage through the DMA
            # tile, nibbles unpack on-chip); every other compressed
            # case — rle, mesh lanes, extraction, the XLA kernel, the
            # chunked-deadline path — inflates exactly the batch's
            # distinct target rows first (the XLA run-start decode:
            # decompress at the point of use, raw rows transient)
            compressed = isinstance(fm_tbl, CompressedFM)
            tile_codec = ("pack4" if (compressed
                                      and fm_tbl.codec == "pack4"
                                      and not self._lane_split
                                      and not extracting
                                      and config.sig_k <= 0)
                          else "raw")
            # kernel selection (DOS_WALK_KERNEL): the XLA walk unless
            # the fused Pallas kernel is asked for by name, which is
            # refused with the reason when it cannot run at this shape.
            # The choice joins the jit key: each kernel compiles (and
            # books its first-call compile time) separately.
            call_q = (self.astar_chunk
                      if config.time and qpad > self.astar_chunk
                      else qpad)
            # lane-split batches: each device walks call_q / L queries,
            # so the VMEM-fit check sees the PER-LANE working set (the
            # same division CPDOracle._walk_kernel applies per shard)
            kernel = choose_walk_kernel(
                self.dg.n, self.dg.k, int(self.dg.w_pad.shape[0]) - 1,
                max(call_q // self.n_lanes, 1) if self._lane_split
                else call_q, codec=tile_codec)
            use_tile_pack4 = (tile_codec == "pack4"
                              and kernel == "pallas")
            if kernel == "pallas":
                p4 = use_tile_pack4

                def walk_fn(dgx, fmx, r_, s_, t_, w_, valid=None,
                            k_moves=-1):
                    return pallas_walk_batch(dgx, fmx, r_, s_, t_, w_,
                                             valid=valid,
                                             k_moves=k_moves,
                                             packed4=p4)
            else:
                walk_fn = table_search_batch
            (M_WALK_PALLAS if kernel == "pallas" else M_WALK_XLA).inc()
            jit_key = (self.alg, shape_key, config.k_moves, extracting,
                       config.sig_k if config.sig_k > 0 else 0, kernel)
            if self._lane_split:
                # lane programs compile separately from single-device
                # ones (and per lane count): bookkeeping stays split
                jit_key = jit_key + (("lanes", self.n_lanes),)
                M_MESH_WALK.inc()
            fm_walk = fm_tbl
            if compressed:
                M_WALK_COMPRESSED.inc()
                td0 = time.perf_counter()
                if use_tile_pack4:
                    fm_walk = fm_tbl.packed
                else:
                    # inflate the batch's DISTINCT target rows once and
                    # remap the row ids onto the dense block — bounded
                    # by the batch, freed with it; bit-identical to
                    # walking the raw table (tests pin it)
                    urows, rinv = np.unique(rows[:nu],
                                            return_inverse=True)
                    rpad = 1 << (len(urows) - 1).bit_length()
                    rows_u = np.zeros(rpad, np.int32)
                    rows_u[:len(urows)] = urows
                    fm_walk = fm_tbl.decompress_rows(
                        self._place(rows_u))
                    jax.block_until_ready(fm_walk)
                    rows = np.zeros(qpad, np.int32)
                    rows[:nu] = rinv.reshape(-1).astype(np.int32)
                M_DECOMPRESS.observe(time.perf_counter() - td0)
                # compressed programs compile separately (the fm
                # operand's shape/dtype differs per codec + row pad)
                jit_key = jit_key + (
                    ("resident", fm_tbl.codec, int(fm_walk.shape[0])),)
        first_call = jit_key not in self._jit_seen
        if self.alg == "astar":
            deadline = t1 + config.time / 1e9 if config.time else None
            with obs_trace.span("worker.walk"):
                for _ in range(max(config.itrs, 1)):
                    cost, plen, fin, counters = self._answer_astar(
                        queries, config, difffile, deadline=deadline)
                    if deadline is not None and time.perf_counter() > deadline:
                        break
            t2 = time.perf_counter()
            self._finish_search(jit_key, first_call, nq, t2 - t1)
            stats = StatsRow(
                **counters, t_receive=t1 - t0, t_astar=t2 - t1,
                t_search=t2 - t0)
            return cost, plen, fin, stats
        def run_walk(rows_h, s_h, t_h, valid_h):
            """One walk call: split across the worker's mesh lanes when
            active (contiguous per-lane subsets of the est-sorted batch
            under shard_map — each lane runs its own bucket grid through
            the selected kernel unchanged), the plain single-device
            kernel otherwise. Answers are bit-identical either way; the
            unsort below never changes."""
            if self._lane_split:
                from ..parallel.sharded import walk_lanes

                return walk_lanes(
                    self.dg, fm_walk, rows_h, s_h, t_h, valid_h, w_pad,
                    self.mesh, k_moves=config.k_moves, kernel=kernel)
            return walk_fn(
                self.dg, fm_walk, jnp.asarray(rows_h), jnp.asarray(s_h),
                jnp.asarray(t_h), w_pad, valid=jnp.asarray(valid_h),
                k_moves=config.k_moves)

        deadline = t1 + config.time / 1e9 if config.time else None
        if self._t_answered is not None:
            # host time since the previous batch's answers: nothing of
            # this engine's was queued on its device in it
            M_DEVICE_GAP.observe(t1 - self._t_answered)
        with obs_trace.span("worker.walk"):
            for _ in range(max(config.itrs, 1)):
                if deadline is None or qpad <= self.astar_chunk:
                    cost, plen, fin = run_walk(rows, s, t, valid)
                    jax.block_until_ready(fin)
                else:
                    # ns budget truncates INSIDE the batch (reference
                    # semantics: the time limit cuts searches short in the
                    # engine, reference args.py:30-57): the sorted batch
                    # runs in fixed-size chunks — cheap queries first — and
                    # the deadline is checked between chunks. The first
                    # chunk always runs (an expired budget still yields a
                    # minimal answer, same rule as the A* chunk path);
                    # skipped chunks come back unfinished, so `finished`
                    # counts are partial like the reference's.
                    ch = self.astar_chunk         # pow2, divides qpad
                    cost, plen, fin = (np.zeros(qpad, np.int64),
                                       np.zeros(qpad, np.int64),
                                       np.zeros(qpad, bool))
                    # one chunk stays in flight ahead (dispatch k+1, then
                    # block on k): a generous budget keeps most of the
                    # single-call pipelining; truncation granularity is one
                    # extra chunk at worst
                    pending = None       # (slice, async device triple)

                    def _land(entry):
                        sl_p, (c_p, p_p, f_p) = entry
                        jax.block_until_ready(f_p)
                        cost[sl_p], plen[sl_p], fin[sl_p] = (
                            np.asarray(c_p), np.asarray(p_p), np.asarray(f_p))
                    for off in range(0, qpad, ch):
                        if off and time.perf_counter() > deadline:
                            break
                        sl = slice(off, off + ch)
                        outs = run_walk(rows[sl], s[sl], t[sl], valid[sl])
                        if pending is not None:
                            _land(pending)
                        pending = (sl, outs)
                    if pending is not None:
                        _land(pending)
                if deadline is not None and time.perf_counter() > deadline:
                    break
            if config.extract and config.k_moves > 0:
                nodes, moves = extract_paths(
                    self.dg, fm_walk, jnp.asarray(rows), jnp.asarray(s),
                    jnp.asarray(t), k=config.k_moves)
                nodes = np.asarray(nodes[:nu], np.int64)[unsort]
                moves = np.asarray(moves[:nu], np.int64)[unsort]
                if inverse is not None:
                    nodes, moves = nodes[inverse], moves[inverse]
                self.last_paths = (nodes, moves)
            elif config.sig_k > 0:
                # bounded path SIGNATURE for the serving cache's scoped
                # invalidation (RuntimeConfig.sig_k wire extension): the
                # same extraction scan as --extract but decoupled from
                # k_moves, so the walk's move budget — and therefore every
                # answer — is untouched
                nodes, moves = extract_paths(
                    self.dg, fm_walk, jnp.asarray(rows), jnp.asarray(s),
                    jnp.asarray(t), k=int(config.sig_k))
                nodes = np.asarray(nodes[:nu], np.int64)[unsort]
                moves = np.asarray(moves[:nu], np.int64)[unsort]
                if inverse is not None:
                    nodes, moves = nodes[inverse], moves[inverse]
                self.last_paths = (nodes, moves)
        t2 = time.perf_counter()
        self._finish_search(jit_key, first_call, nq, t2 - t1)
        if first_call and obs_device.enabled():
            # one XLA cost/memory analysis per compiled-program key
            # (FLOPs, bytes accessed, HBM footprint -> /metrics gauges +
            # BENCH_DETAIL.json): the AOT re-lower is cheap and runs
            # once, outside the timed search interval — the roofline
            # evidence ROADMAP item 1 is judged against. The analyzed
            # shape is the search program the loop above ACTUALLY ran
            # (chunk-wide whenever the deadline path chunked — which,
            # unlike shape_key, it does even under --extract), so the
            # lower/compile is a cache hit, never a fresh compile of a
            # never-executed shape
            cap_n = (self.astar_chunk
                     if deadline is not None and qpad > self.astar_chunk
                     else qpad)
            sl = slice(0, cap_n)
            if self._lane_split:
                # the mesh path ran the lane-split shard_map program,
                # not the single-device one — lower THAT (the roofline
                # gauges used to go dark on meshed workers). The helper
                # hands back the SAME cached jit walk_lanes dispatched,
                # with operands lane-sharded exactly as it shipped them,
                # so the AOT lower/compile is an XLA cache hit; the key
                # carries the lane count because lane programs compile
                # per lane count (the jit_key says the same)
                from ..parallel.sharded import lane_walk_program
                tag = "[pallas]" if kernel == "pallas" else ""
                fn_l, ops_l = lane_walk_program(
                    self.dg, fm_walk, rows[sl], s[sl], t[sl],
                    valid[sl], w_pad, self.mesh,
                    k_moves=config.k_moves, kernel=kernel)
                obs_device.capture(
                    f"table-search{tag}[lanes{self.n_lanes}]"
                    f"/q{cap_n}/k{config.k_moves}",
                    fn_l, *ops_l)
            elif kernel == "pallas":
                # the fused kernel's statics live in a closure so the
                # capture's AOT lower sees only array operands (its
                # interpret/bucket resolution runs at trace time)
                km = config.k_moves
                p4c = use_tile_pack4

                def _cap_fn(dgx, fmx, r_, s_, t_, w_, v_):
                    return pallas_walk_batch(dgx, fmx, r_, s_, t_, w_,
                                             valid=v_, k_moves=km,
                                             packed4=p4c)

                obs_device.capture(
                    f"table-search[pallas]/q{cap_n}/k{config.k_moves}",
                    _cap_fn, self.dg, fm_walk, jnp.asarray(rows[sl]),
                    jnp.asarray(s[sl]), jnp.asarray(t[sl]), w_pad,
                    jnp.asarray(valid[sl]))
            else:
                obs_device.capture(
                    f"table-search/q{cap_n}/k{config.k_moves}",
                    table_search_batch, self.dg, fm_walk,
                    jnp.asarray(rows[sl]), jnp.asarray(s[sl]),
                    jnp.asarray(t[sl]), w_pad,
                    valid=jnp.asarray(valid[sl]), k_moves=config.k_moves)

        # the answers to the host in request order: three device slices,
        # their transfers, the unsort and the fan-out. The walk's
        # interval (t_astar, worker_search_seconds) ends before this
        tf = time.perf_counter()
        with obs_trace.span("worker.fetch"):
            cost = np.asarray(cost[:nu], np.int64)[unsort]
            plen = np.asarray(plen[:nu], np.int64)[unsort]
            fin = np.asarray(fin[:nu], bool)[unsort]
            if inverse is not None:
                # fan deduped answers back out to every original query
                # — the stats sums below stay per ORIGINAL query by
                # summing AFTER this expansion
                cost, plen, fin = (cost[inverse], plen[inverse],
                                   fin[inverse])
        self._t_answered = time.perf_counter()
        M_FETCH.observe(self._t_answered - tf)
        stats = StatsRow(
            n_expanded=int(plen.sum()),   # node expansions = moves walked
            n_touched=nq,
            plen=int(plen.sum()),
            finished=int(fin.sum()),
            t_receive=t1 - t0,
            t_astar=t2 - t1,
            t_search=t2 - t0,
        )
        return cost, plen, fin, stats

    def _finish_search(self, jit_key: tuple, first_call: bool, nq: int,
                       seconds: float) -> None:
        """Book one batch's search interval: first call at a new program
        key goes to the compile histogram (XLA compilation dominates it),
        repeats to the steady-state one."""
        self._jit_seen.add(jit_key)
        (M_JIT if first_call else M_SEARCH).observe(seconds)
        if not first_call:
            # live window mirrors the steady-state histogram (a cold
            # compile would own the window's p99 for a whole rotation);
            # the exemplar id is the batch's wire trace id when set
            obs_quantiles.observe(
                "worker_search_seconds", seconds,
                trace_id=obs_trace.current_trace_id())
        M_BATCHES.inc()
        M_QUERIES.inc(nq)

    def _raw_weights_for(self, difffile: str, no_cache: bool):
        """Raw (unpadded) query weights + heuristic scale, cached per diff
        like the device-side weight cache."""
        from ..models.astar import min_cost_per_unit

        key = ("raw", difffile)
        if key in self._weight_cache and not no_cache:
            self._weight_cache.move_to_end(key)
            return self._weight_cache[key]
        w = (self.graph.w if difffile == "-"
             else self.graph.weights_with_diff(read_diff(difffile)))
        entry = (w, min_cost_per_unit(self.graph, w))
        if no_cache:
            self._weight_cache.pop(key, None)
        else:
            self._weight_cache[key] = entry
            self._trim_weight_cache()
        return entry

    def _answer_astar(self, queries: np.ndarray, config: RuntimeConfig,
                      difffile: str = "-", deadline: float | None = None):
        """hscale/fscale weighted A* — the serving path is the **batched
        device kernel** (``ops.batched_astar``): the whole batch searches
        in lock-step sweeps, chunked to bound the working set, with the
        ``time`` deadline checked between chunks — the FIRST chunk always
        runs (an expired budget still yields a minimal answer, like the
        per-query CPU oracle), remaining chunks stay unfinished. ``config.debug`` instead runs the
        per-query CPU heap oracle (``models.astar``) — the deterministic,
        expansion-order-faithful repro path, matching the reference's
        debug mode forcing single-threaded runs (reference
        ``offline.py:143-147``).

        Honors ``hscale``/``fscale``/``itrs``/``time``/``no_cache``.
        ``k_moves`` is deliberately NOT applied: per the reference,
        "K-moves are only available with extractions while hScale only
        influences A*" (reference ``args.py:28``).
        """
        if not config.debug:
            from ..ops.batched_astar import astar_batch_np

            w, cpu = self._raw_weights_for(difffile, config.no_cache)
            if config.no_cache:
                # no_cache = re-read the diff from disk next time; stale
                # device copies keyed by the diff path must go too
                for k in [k for k in self._astar_ctx
                          if isinstance(k, tuple) and k[0] == "w_pad"]:
                    del self._astar_ctx[k]
            cost, plen, fin, counters = astar_batch_np(
                self.graph, queries, w, hscale=config.hscale,
                fscale=config.fscale, deadline=deadline, cpu=cpu,
                chunk=self.astar_chunk, ctx=self._astar_ctx,
                w_key=None if config.no_cache else difffile)
            counters["plen"] = int(plen.sum())
            counters["finished"] = int(fin.sum())
            return cost, plen, fin, counters

        from ..models.astar import AstarStats, astar

        w, cpu = self._raw_weights_for(difffile, config.no_cache)
        st = AstarStats()
        cost = np.zeros(len(queries), np.int64)
        plen = np.zeros(len(queries), np.int64)
        fin = np.zeros(len(queries), bool)
        for i, (s, t) in enumerate(queries):
            if deadline is not None and time.perf_counter() > deadline:
                break
            cost[i], plen[i], fin[i] = astar(
                self.graph, int(s), int(t), w, hscale=config.hscale,
                fscale=config.fscale, cpu=cpu, stats=st)
        counters = dict(
            n_expanded=st.n_expanded, n_inserted=st.n_inserted,
            n_touched=st.n_touched, n_updated=st.n_updated,
            n_surplus=st.n_surplus, plen=st.plen, finished=st.finished)
        return cost, plen, fin, counters
