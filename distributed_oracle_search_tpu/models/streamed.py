"""Streamed CPD serving: answer campaigns whose index exceeds HBM.

The resident :class:`~..models.cpd.CPDOracle` holds the whole ``[W, R, N]``
first-move tensor on the mesh — perfect until ``N^2 / W`` outgrows HBM
(~16 GB on v5e: a 264k-node graph is a 70 GB single-shard table; the
reference-scale regime of BASELINE.md configs[4-5]). The reference never
faces this because its run-length-compressed CPD lives in host RAM and is
pointer-chased per query (reference ``make_fifos.py:21``, SURVEY.md §C5);
the TPU answer is **streaming**: keep the index on disk (the per-block
``.npy`` checkpoint files ARE the serving format), and per batch upload
only the fm rows the batch actually targets, in bounded row-chunks.

A random scenario of Q queries touches ≤ Q distinct target rows — usually
far fewer than R — and each uploaded ``[C, N]`` chunk answers every query
aimed at those rows in one device walk. Row-chunks are ordered
block-contiguously so the host-side gather reads each mmapped block file
sequentially. Chunk size and padded query counts are compile-stable
(powers of two), so a resident server reuses a handful of programs.

This is deliberately a single-device serving mode: multi-chip scale-out
uses the resident sharded oracle (sharding IS the memory plan); streaming
is the fallback when one chip must serve an index bigger than its HBM,
and the two share the same walk kernel and wire semantics.

Cold chunks upload 4-bit packed — half the host-to-device bytes — with
a one-pass device unpack per chunk. High
ELL slots (≥ 14, hub-node rarities) ride a tiny per-chunk exception
list scattered after the unpack, so packing is degree-independent.
Uploaded row-chunks are kept on device in a bounded LRU
(``cache_bytes``):
campaigns whose targets overlap earlier ones — the resident-server usage
pattern, one request round per diff (reference ``process_query.py:178``) —
skip the upload entirely and run at near-resident speed. Range chunks key
on their row range; compacted chunks are content-addressed by row-id
digest (an identical chunk — a replayed campaign — hits). Keys are
independent of the query-time weights: a diff round re-uses every chunk
the free-flow round uploaded, because fm rows hold free-flow FIRST MOVES
while diffs only change the cost accumulation (``ops.table_search``
semantics).
"""

from __future__ import annotations

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..data.graph import Graph
from ..ops import DeviceGraph
from ..ops.table_search import (
    extract_paths, table_search_batch, table_search_multi,
)
from ..parallel.partition import DistributionController
from .cpd import length_estimate, shard_block_name, validate_manifest
from ..utils.env import env_cast, env_flag
from ..utils.log import get_logger

log = get_logger(__name__)


def _pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


#: 4-bit packed uploads: slots 0..13 pack directly into a nibble,
#: 0xF is the -1 "no move" marker, and 0xE escapes to a per-chunk
#: exception list (row, col, true slot) scattered on device after the
#: nibble unpack — so packing works for ANY degree, at half the wire
#: bytes plus ~6 bytes per exceptional entry. Entries with slot >= 14
#: exist only at hub nodes whose shortest path leaves by a high ELL
#: slot (measured <0.5% of entries on the 264k road graph), so the
#: escape traffic is noise. DOS_STREAM_PACK4=0 disables. Packing is
#: skipped only when exceptions stop being rare (the break-even where
#: escape bytes eat the nibble savings).
PACK4_ESCAPE = 14
PACK4_MARKER = 15
#: skip packing when more than this fraction of a chunk's entries
#: escape. Break-even arithmetic: the nibble saves 0.5 bytes/entry;
#: one exception costs 7 bytes (uint16 row + int32 col + int8 val),
#: up to ~14 with the pow2 padding — 0.5 / 14 ≈ 3.5%, rounded down
#: (real road graphs measure ~0.1%)
PACK4_MAX_ESCAPE_FRAC = 0.03


@functools.partial(jax.jit, static_argnames=("n",))
def _unpack4(packed: jnp.ndarray, n: int, exc_r: jnp.ndarray,
             exc_c: jnp.ndarray, exc_v: jnp.ndarray) -> jnp.ndarray:
    """[C, ceil(N/2)] uint8 nibbles -> [C, N] int8 fm.

    0xF -> -1; 0xE entries are overwritten by the scattered exception
    triples. Pad triples are ``(0, 0, fm[0, 0])`` identity writes —
    they re-write position (0, 0)'s true value, so the scatter is
    idempotent whether or not (0, 0) itself escapes."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    c = packed.shape[0]
    v = jnp.stack([lo, hi], axis=-1).reshape(c, -1)[:, :n]
    v = v.astype(jnp.int8)
    v = jnp.where(v == PACK4_MARKER, jnp.int8(-1), v)
    return v.at[exc_r, exc_c].set(exc_v)


def _pack4(fm_np: np.ndarray):
    """[C, N] int8 fm -> (packed nibbles, exc_rows, exc_cols, exc_vals)
    or None when too many entries escape (degenerate packing)."""
    if fm_np.shape[0] > 65536:
        # escape rows are uint16; a taller chunk would silently wrap
        # the scatter indices and corrupt unpacked moves — fall back
        return None
    esc_r, esc_c = np.nonzero(fm_np >= PACK4_ESCAPE)
    if len(esc_r) > PACK4_MAX_ESCAPE_FRAC * fm_np.size:
        return None
    a = fm_np.astype(np.uint8)
    a = np.where(fm_np < 0, np.uint8(PACK4_MARKER),
                 np.minimum(a, PACK4_ESCAPE))
    if a.shape[1] % 2:
        a = np.concatenate(
            [a, np.full((a.shape[0], 1), np.uint8(PACK4_MARKER))],
            axis=1)
    packed = a[:, 0::2] | (a[:, 1::2] << 4)
    exc_v = fm_np[esc_r, esc_c]
    # pad the exception list to a power of two so one compiled unpack
    # program serves many chunks; pads are (0, 0, fm[0, 0]) identity
    # writes (see _unpack4). uint16 rows: the chunk axis is bounded by
    # row_chunk << 65536; cols span N and need int32.
    cap = 1 << max(int(len(esc_r)) - 1, 0).bit_length()
    cap = max(cap, 1)
    er = np.zeros(cap, np.uint16)
    ec = np.zeros(cap, np.int32)
    ev = np.full(cap, fm_np[0, 0], np.int8)
    er[:len(esc_r)] = esc_r
    ec[:len(esc_r)] = esc_c
    ev[:len(esc_r)] = exc_v
    return packed, er, ec, ev


#: Transposed run-length wire coding. The reference's whole compression
#: premise is that CPD tables are run-heavy (its RLE rows measure 50-100x
#: on road networks, ``native/src/cpd.hpp``) — but OUR rows run along the
#: wrong axis for that: a ``[C, N]`` chunk's row is "first move toward
#: one target FROM every source", and adjacent sources' ELL slot numbers
#: are uncorrelated (measured mean run length 1.5-2.5). The coherence
#: lives on the TARGET axis: nearby targets (owned rows are
#: block-contiguous, RCM/grid ordered) are reached the same way from
#: almost every source — measured 93-97% of entries equal the entry one
#: target-row up, mean column-run length 14-34. So the wire format RLE's
#: the TRANSPOSED chunk: per source column, runs of consecutive target
#: rows sharing a first move.
#:
#: Wire layout (flat, no per-column padding — run counts are skewed and
#: padding to the max would eat the win): ``lens`` uint8 run lengths in
#: column-major order (runs > 255 split), ``vals`` int8 run first-moves,
#: ``counts`` int32 runs per column — ~2 bytes per run + 4 per column.
#: Device decode is one scatter-add of value DELTAS at global run starts
#: into a [N*C] zeros buffer, a cumsum (deltas telescope: any contiguous
#: partial sum is val_b - val_a, bounded +-255, so int16 accumulation is
#: exact), an int8 cast, and a transpose — O(N*C) streaming work, no
#: searchsorted over the output. DOS_STREAM_RLE=0 disables; chunks fall
#: back per-chunk to pack4/raw when runs are too short to pay
#: (RLE_MAX_FRAC of the best dense alternative).
#:
#: The encoding is PERSISTED: the host-side encode is a few full passes
#: over the raw chunk (~8 s for a 419 MB chunk — it would dominate the
#: cold round it exists to speed up), so the first miss writes the wire
#: triple as an ``rle-*.npz`` sidecar next to the block files,
#: fingerprinted against the source blocks' (size, mtime). Later cold
#: rounds read the ~30 MB sidecar instead of the 1.7 GB raw rows — disk
#: traffic shrinks by the same factor as the wire. This mirrors the
#: reference, whose CPD files are THEMSELVES stored run-length
#: compressed and loaded compressed at server start (reference
#: README.md CPD description). DOS_STREAM_RLE_SIDECAR=0 disables
#: persistence (encode-on-the-fly each time); sidecar writes are
#: best-effort (read-only index dirs just skip them).
RLE_MAX_FRAC = 0.9


def _pack_rle(fm_np: np.ndarray, pack4_viable: bool):
    """[C, N] int8 fm -> (lens u8 [T], vals i8 [T], counts i32 [N]) in
    TRANSPOSED (column-major, target-axis-runs) order, or None when the
    encoding would not beat the best dense upload (pack4 when viable,
    else raw)."""
    c, n = fm_np.shape
    if c < 2 or n == 0:
        return None
    dense = fm_np.size // 2 if pack4_viable else fm_np.size
    # cheap reject BEFORE the transposed copy: the total run count is
    # countable straight off the row-major array (runs only grow after
    # the 255-splits, so an over-budget count here is final) — an
    # incompressible chunk then costs one compare pass, not three
    # full-size passes plus a 400 MB transpose
    runs_min = int(np.count_nonzero(fm_np[1:] != fm_np[:-1])) + n
    if 2 * (1 << max(runs_min - 1, 0).bit_length()) + 4 * n >= \
            RLE_MAX_FRAC * dense:
        return None
    a = np.ascontiguousarray(fm_np.T)                    # [N, C]
    ch = np.empty((n, c), bool)
    ch[:, 0] = True
    ch[:, 1:] = a[:, 1:] != a[:, :-1]
    idx = np.flatnonzero(ch.reshape(-1))                 # run starts
    # exact budget after the 255-splits; each run costs 2 wire bytes
    # (+ the fixed 4/column); the dense alternative is n*c/2 (pack4)
    # or n*c (raw)
    lengths = np.diff(idx, append=n * c)
    pieces = -(-lengths // 255)                          # uint8 splits
    tot = int(pieces.sum())
    cap = 1 << max(tot - 1, 0).bit_length()
    wire = 2 * cap + 4 * n
    if wire >= RLE_MAX_FRAC * dense:
        return None
    flat_vals = a.reshape(-1)[idx]
    plen = np.full(cap, 0, np.uint8)
    pval = np.full(cap, flat_vals[-1] if len(flat_vals) else 0, np.int8)
    # split runs longer than 255 into 255-length pieces + remainder;
    # continuation pieces repeat the run's value (delta 0 on device)
    last = np.cumsum(pieces) - 1
    pl = np.full(tot, 255, np.uint8)
    pl[last] = (lengths - 255 * (pieces - 1)).astype(np.uint8)
    plen[:tot] = pl
    pval[:tot] = np.repeat(flat_vals, pieces)
    counts = np.bincount(np.repeat(idx // c, pieces),
                         minlength=n).astype(np.int32)
    return plen, pval, counts


@functools.partial(jax.jit, static_argnames=("c",))
def _unpack_rle(plen: jnp.ndarray, vals: jnp.ndarray,
                counts: jnp.ndarray, c: int) -> jnp.ndarray:
    """Transposed-RLE wire triple -> [C, N] int8 fm.

    Pad runs (length 0, value = last real value) decode to delta 0 at an
    out-of-range start and are dropped by the scatter."""
    n = counts.shape[0]
    t = plen.shape[0]
    pl = plen.astype(jnp.int32)
    s = jnp.cumsum(pl) - pl                              # exclusive
    coff = jnp.cumsum(counts) - counts                   # exclusive
    col = jnp.searchsorted(coff, jnp.arange(t), side="right") - 1
    g_start = col * c + s - s[coff[col]]
    v16 = vals.astype(jnp.int16)
    delta = v16 - jnp.concatenate([jnp.zeros(1, jnp.int16), v16[:-1]])
    out = jnp.zeros(n * c, jnp.int16).at[g_start].add(delta, mode="drop")
    return jnp.cumsum(out).astype(jnp.int8).reshape(n, c).T


def default_cache_bytes() -> int:
    """Device-residency budget for cached fm row-chunks: a quarter of
    the device's reported memory (4 GB on a 16 GB v5e — enough to hold a
    whole 102k-node worker shard, 1.3 GB, with room to spare, while
    never crowding out the walk state). Streaming exists for indexes
    bigger than HBM, so the cache must scale DOWN with the chip, not
    assume one: a TPU that reports no memory limit is an error. Only
    host backends, which report none, get 1 GiB."""
    device = jax.local_devices()[0]
    limit = int((device.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit // 4
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} reports no memory limit; pass the "
            "stream cache budget explicitly (cache_bytes)")
    return 1 << 30


class StreamedCPDOracle:
    """Serve table-search queries from an on-disk CPD index, streaming
    only the rows each batch needs.

    Parameters
    ----------
    graph      : the (free-flow) road graph
    controller : partition controller — must match the built index
    outdir     : CPD index directory (``index.json`` + block files)
    row_chunk  : fm rows resident per upload; the device-memory knob.
                 Working set ≈ ``row_chunk * N`` bytes of int8 fm plus the
                 walk state — e.g. 4096 rows x 264k nodes ≈ 1.1 GB.
    cache_bytes: device bytes of uploaded fm chunks kept in an LRU
                 across :meth:`query` calls (0 disables; None — the
                 default — resolves via :func:`default_cache_bytes`,
                 a quarter of the device's memory). Campaigns with
                 overlapping targets — including every diff round
                 after the first — skip the re-upload.
    """

    def __init__(self, graph: Graph, controller: DistributionController,
                 outdir: str, row_chunk: int = 4096,
                 cache_bytes: int | None = None):
        self.graph = graph
        self.dc = controller
        self.outdir = outdir
        self.row_chunk = int(row_chunk)
        self.cache_bytes = (default_cache_bytes() if cache_bytes is None
                            else int(cache_bytes))
        self.dg = DeviceGraph.from_graph(graph)
        with open(os.path.join(outdir, "index.json")) as f:
            manifest = json.load(f)
        validate_manifest(manifest, controller, outdir)
        self._blocks: dict[tuple[int, int], np.ndarray] = {}
        # bounded LRU of DECODED compressed blocks (see _block);
        # insertion order is the recency order
        self._decoded: dict[tuple[int, int], np.ndarray] = {}
        # LRU of device-resident [C, N] chunks, key (wid, r0); insertion
        # order IS the recency order (moved-to-end on hit)
        self._chunk_cache: dict[tuple[int, int], jnp.ndarray] = {}
        #: 4-bit packed uploads — HALF the upload bytes on cold chunks
        #: (device unpacks once per upload; the cache holds the unpacked
        #: chunk, so warm rounds are unchanged). High slots ride a tiny
        #: exception list, so this is degree-independent; a chunk whose
        #: escape fraction is degenerate falls back to raw per-chunk.
        self.pack4 = env_flag("DOS_STREAM_PACK4", True)
        #: transposed target-axis RLE — the cold path's big lever
        #: (~7-17x fewer wire bytes measured on road/city chunks vs the
        #: raw fm, vs pack4's fixed 2x); falls back per-chunk via
        #: :func:`_pack_rle`'s break-even check
        self.rle = env_flag("DOS_STREAM_RLE", True)
        #: persist encodings as npz sidecars in the index dir (see the
        #: module-level RLE notes); the first cold round pays the encode,
        #: every later one streams straight off the compressed sidecar
        self.rle_sidecar = (self.rle
                            and env_flag("DOS_STREAM_RLE_SIDECAR", True))
        #: telemetry of the most recent :meth:`query` call
        self.last_stats: dict = {}

    def clear_cache(self) -> None:
        """Drop every device-resident cached chunk (frees device memory;
        the next campaign re-streams from disk)."""
        self._chunk_cache.clear()

    def _cache_get(self, key):
        hit = self._chunk_cache.pop(key, None)
        if hit is not None:
            self._chunk_cache[key] = hit          # refresh recency
        return hit

    def _cache_put(self, key, fm_d: jnp.ndarray) -> None:
        if self.cache_bytes <= 0 or fm_d.nbytes > self.cache_bytes:
            return
        held = sum(v.nbytes for v in self._chunk_cache.values())
        while self._chunk_cache and held + fm_d.nbytes > self.cache_bytes:
            old = self._chunk_cache.pop(
                next(iter(self._chunk_cache)))    # evict least-recent
            held -= old.nbytes
        self._chunk_cache[key] = fm_d

    def _chunk_fingerprint(self, pairs) -> np.ndarray:
        """Stat fingerprint of the block files a chunk reads from:
        ``[bytes, mtime_ns]`` per (wid, bid) pair, ordered. A rebuilt
        index changes it, invalidating any persisted sidecar."""
        out = []
        for wid, bid in pairs:
            st = os.stat(os.path.join(self.outdir,
                                      shard_block_name(wid, bid)))
            out.append((st.st_size, st.st_mtime_ns))
        return np.asarray(out, np.int64)

    def _sidecar_load(self, path: str, fp: np.ndarray):
        """RLE wire triple from a sidecar; ``"fallback"`` when a valid
        sidecar records that this chunk measured incompressible (so the
        multi-pass encode attempt is not re-paid every cold round);
        None when absent / stale / unreadable."""
        try:
            with np.load(path) as z:
                if (z["fp"].shape == fp.shape
                        and (z["fp"] == fp).all()):
                    if "fallback" in z:
                        return "fallback"
                    return z["lens"], z["vals"], z["counts"]
        except Exception as e:  # noqa: BLE001 — corrupt zip, missing
            # keys, IO: any failure means "re-encode", never raise
            log.debug("RLE sidecar %s unusable (%s); re-encoding",
                      path, e)
        return None

    def _sidecar_save(self, path: str, fp: np.ndarray, enc) -> None:
        """Best-effort atomic persist (tmp + rename); read-only index
        dirs and races just skip. ``enc=None`` persists a negative
        marker (chunk measured incompressible)."""
        tmp = f"{path}.{os.getpid()}.tmp.npz"       # savez keeps .npz
        try:
            if enc is None:
                np.savez(tmp, fp=fp, fallback=np.int8(1))
            else:
                np.savez(tmp, fp=fp, lens=enc[0], vals=enc[1],
                         counts=enc[2])
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)     # don't leak tmp files on a full disk
            except OSError:
                pass

    #: decoded compressed blocks kept host-side at once. The streamed
    #: oracle's whole contract is a bounded working set (row_chunk * N
    #: plus handles) — caching every decoded block would silently
    #: re-materialize the raw table exactly when compression matters
    #: most. Chunks read block-contiguously, so a tiny LRU keeps the
    #: within-chunk locality and a swept campaign stays bounded.
    _DECODED_KEEP = 4

    def _block(self, wid: int, bid: int) -> np.ndarray:
        """Memory-mapped block file (cached handle, not cached data).

        Compressed-container blocks (``models.resident``) decode on
        touch — the streamed row reads need dense rows — but the
        DECODED copies live in a small LRU (``_DECODED_KEEP``), not
        the unbounded handle cache: raw mmap handles cost pages, a
        decoded block costs its full dense bytes. The mmap's
        page-cache-speed contiguous reads apply to raw blocks only."""
        from .resident import is_container, maybe_decode_rows

        key = (wid, bid)
        hit = self._decoded.pop(key, None)
        if hit is not None:
            self._decoded[key] = hit          # refresh recency
            return hit
        if key not in self._blocks:
            self._blocks[key] = np.load(
                os.path.join(self.outdir, shard_block_name(wid, bid)),
                mmap_mode="r")
        arr = self._blocks[key]
        if is_container(arr):
            arr = maybe_decode_rows(arr)
            self._decoded[key] = arr
            while len(self._decoded) > self._DECODED_KEEP:
                self._decoded.pop(next(iter(self._decoded)))
        return arr

    def _row_range(self, wid: int, r0: int, count: int) -> np.ndarray:
        """Contiguous owned-row slice [count, N] (tail-padded with stuck
        rows past the worker's last row). Contiguous mmap reads stream at
        disk/page-cache speed — measured 7 GB/s vs 0.2 GB/s for
        row-by-row fancy indexing on the same file — which is why the
        dense serving mode uploads ranges instead of compacted row sets.
        """
        bs = self.dc.block_size
        n_owned = self.dc.n_owned(wid)
        hi = min(r0 + count, n_owned)
        parts = []
        r = r0
        while r < hi:
            bid = r // bs
            stop = min(hi, (bid + 1) * bs)
            parts.append(self._block(wid, bid)[r - bid * bs:
                                               stop - bid * bs])
            r = stop
        if len(parts) == 1 and hi - r0 == count:
            return parts[0]           # zero-copy view of the mmap
        out = np.full((count, self.graph.n), -1, np.int8)
        if parts:
            seg = parts[0] if len(parts) == 1 else np.concatenate(parts)
            out[:hi - r0] = seg
        return out

    def _gather_rows(self, wids: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Host-side gather of fm rows (wid, owned-row) -> [C, N] int8."""
        bs = self.dc.block_size
        out = np.empty((len(rows), self.graph.n), np.int8)
        bids = rows // bs
        # group by (wid, bid) so each mmapped file is fancy-indexed once
        order = np.lexsort((rows, bids, wids))
        i = 0
        while i < len(order):
            j = i
            wid, bid = wids[order[i]], bids[order[i]]
            while (j < len(order) and wids[order[j]] == wid
                   and bids[order[j]] == bid):
                j += 1
            sel = order[i:j]
            out[sel] = self._block(int(wid), int(bid))[rows[sel] - bid * bs]
            i = j
        return out

    def query(self, queries: np.ndarray, w_query: np.ndarray | None = None,
              k_moves: int = -1, max_steps: int = 0):
        """Answer (s, t) queries in input order: ``(cost, plen, finished)``.

        Matches the resident oracle's :meth:`~.CPDOracle.query` semantics
        exactly (tests pin this); only the memory plan differs.
        """
        w_pad = (self.dg.w_pad if w_query is None
                 else jnp.asarray(self.graph.padded_weights(w_query),
                                  jnp.int32))
        return self._campaign(queries, w_pad, None, k_moves, max_steps)

    def query_paths(self, queries: np.ndarray, k: int):
        """Materialize each query's first ``k`` path nodes from the
        streamed index (the reference's ``--k-moves`` extraction,
        reference ``args.py:31-36``) — per-chunk :func:`extract_paths`
        on the uploaded fm rows, which are already device-resident for
        the walk, so extraction costs one extra scan per chunk and no
        extra bytes. Returns ``(nodes int64 [Q, k+1], moves int64 [Q])``
        with the resident :meth:`~.CPDOracle.query_paths` semantics.
        """
        if k <= 0:
            raise ValueError("k must be positive")
        return self._campaign(queries, self.dg.w_pad, None, -1, 0,
                              paths_k=k)

    def query_multi(self, queries: np.ndarray,
                    w_diffs: list[np.ndarray | None], max_steps: int = 0):
        """Answer queries under D congestion diffs in ONE streamed pass.

        The fused analog of :meth:`~.CPDOracle.query_multi` for the
        streamed memory plan: each uploaded chunk is walked once and
        every diff's costs accumulate together — and with the device
        LRU, a fused D-round campaign after a free-flow round both
        streams zero bytes AND walks once. Returns ``(cost [D, Q],
        plen [Q], finished [Q])`` in input order.
        """
        if not w_diffs:
            raise ValueError("w_diffs must name at least one round")
        w_pads = jnp.asarray(self.graph.padded_weights_multi(w_diffs))
        return self._campaign(queries, None, w_pads, -1, max_steps)

    def _campaign(self, queries, w_pad, w_pads_multi, k_moves, max_steps,
                  paths_k: int = 0):
        """Shared streamed-campaign driver; ``w_pads_multi`` non-None
        selects the fused multi-diff kernel (cost rows per diff);
        ``paths_k`` > 0 selects path-prefix extraction instead of the
        cost walk (returns ``(nodes, moves)``)."""
        queries = np.asarray(queries, np.int64)
        nq = len(queries)
        s_all, t_all = queries[:, 0], queries[:, 1]
        n_multi = (0 if w_pads_multi is None
                   else int(w_pads_multi.shape[0]))

        # distinct targets, ordered block-contiguously for the host gather
        uniq_t, inv = np.unique(t_all, return_inverse=True)
        u_wid = self.dc.worker_of(uniq_t)
        u_row = self.dc.owned_index_of(uniq_t)
        c = self.row_chunk

        # ---- chunking mode. Dense campaigns upload CONTIGUOUS row
        # ranges straight off the mmap — zero host row copies (measured
        # 7 GB/s vs 0.2 GB/s for fancy-index row gathers). Sparse
        # campaigns compact the distinct rows instead — fewer uploaded
        # bytes. Break-even: range wins when density >
        # copy_bw / (copy_bw + upload_bw); the 0.45 default has not been
        # measured on a chip attached to its host (a fast host-to-device
        # link pushes it lower). DOS_STREAM_RANGE_DENSITY overrides.
        thresh = env_cast("DOS_STREAM_RANGE_DENSITY", 0.45, float)
        n_range = max(-(-max(self.dc.max_owned, 1) // c), 1)
        rkey = u_wid.astype(np.int64) * n_range + u_row // c
        uniq_key = np.unique(rkey)
        density = (len(uniq_t) / (len(uniq_key) * c)
                   if len(uniq_key) else 1.0)
        range_mode = density >= thresh

        if range_mode:
            chunk_of_uniq = np.searchsorted(uniq_key, rkey)
            r0_of_chunk = (uniq_key % n_range) * c
            wid_of_chunk = uniq_key // n_range
            q_chunk = chunk_of_uniq[inv]
            q_row = u_row[inv] - r0_of_chunk[q_chunk]
            n_chunks = len(uniq_key)
        else:
            u_order = np.lexsort((u_row, u_wid))
            pos_of_uniq = np.empty(len(uniq_t), np.int64)
            pos_of_uniq[u_order] = np.arange(len(uniq_t))
            q_pos = pos_of_uniq[inv]          # stream position per query
            q_chunk = q_pos // c
            q_row = q_pos % c
            n_chunks = -(-len(uniq_t) // c) if len(uniq_t) else 0

        if paths_k:
            out_nodes = np.zeros((nq, paths_k + 1), np.int64)
        out_c = np.zeros((n_multi, nq) if n_multi else nq, np.int64)
        out_p = np.zeros(nq, np.int64)
        out_f = np.zeros(nq, bool)
        bytes_streamed = 0
        bytes_raw = 0
        cache_hits = 0
        cache_misses = 0
        chunks_packed = 0
        chunks_rle = 0
        sidecar_hits = 0
        # one sort up front; each chunk's queries are then a slice (the
        # serving hot path must not rescan all Q queries per chunk)
        q_by_chunk = np.argsort(q_chunk, kind="stable")
        # ONE padded query shape for the whole campaign (the max chunk,
        # rounded up): per-chunk pow2 padding would compile a fresh walk
        # program per distinct chunk size
        if n_chunks:
            bounds = np.searchsorted(
                q_chunk[q_by_chunk], np.arange(n_chunks + 1))
            qp_all = _pow2(int(np.diff(bounds).max()))

        def prep(ci):
            """Host read + padding + device upload (async enqueue) for
            one chunk; chunks come from / land in the device LRU so
            overlapping campaigns skip the upload. Range chunks key on
            their row range; compacted chunks (arbitrary row sets) are
            content-addressed by the row-id digest, so only an identical
            chunk repeats — e.g. a replayed or per-diff-round campaign."""
            nonlocal bytes_streamed, bytes_raw, cache_hits, \
                cache_misses, chunks_packed, chunks_rle, sidecar_hits
            if range_mode:
                wid_c, r0_c = int(wid_of_chunk[ci]), int(r0_of_chunk[ci])
                key = (wid_c, r0_c, c)
            else:
                take = u_order[ci * c:(ci + 1) * c]
                key = ("compacted", c,
                       hashlib.blake2b(u_wid[take].tobytes()
                                       + u_row[take].tobytes(),
                                       digest_size=16).digest())
            fm_dev = self._cache_get(key)
            if fm_dev is not None:
                cache_hits += 1
            else:
                cache_misses += 1
                # persisted-RLE fast path: a valid sidecar skips the
                # raw block read AND the encode — the cold round's two
                # dominant costs once the wire itself is small
                # sidecars persist for RANGE chunks only: their names
                # are bounded by the index's row ranges. Compacted
                # chunks are content-addressed per campaign row set —
                # persisting those would grow the index dir without
                # bound as query sets vary (each unseen set a new file,
                # never pruned); they re-encode per miss instead.
                sc_path = fp = rk = None
                if self.rle_sidecar and range_mode:
                    bs = self.dc.block_size
                    hi = min(r0_c + c, self.dc.n_owned(wid_c))
                    pairs = [(wid_c, b) for b in
                             range(r0_c // bs, (hi - 1) // bs + 1)]
                    sc_path = os.path.join(
                        self.outdir,
                        f"rle-w{wid_c:05d}-r{r0_c:09d}-c{c}.npz")
                    fp = self._chunk_fingerprint(pairs)
                    rk = self._sidecar_load(sc_path, fp)
                    if rk is not None:
                        sidecar_hits += 1
                skip_rle = rk == "fallback"
                if skip_rle:
                    rk = None
                if rk is None:
                    if range_mode:
                        fm_np = self._row_range(wid_c, r0_c, c)
                    else:
                        fm_np = self._gather_rows(u_wid[take],
                                                  u_row[take])
                        if len(take) < c:     # stable chunk shape: pad
                            fm_np = np.concatenate(  # with stuck rows
                                [fm_np,
                                 np.full((c - len(take), self.graph.n),
                                         -1, np.int8)])
                    # wire coding, best first: transposed RLE (~7-17x),
                    # then 4-bit pack (2x), then raw — each falls back
                    # per-chunk when its break-even check fails.
                    # RLE's break-even baseline optimistically assumes
                    # pack4 will succeed whenever it is enabled (the
                    # escape-heavy chunks where it would not are the
                    # rare <0.5% hub case); computing the real escape
                    # count here would add a full chunk pass that
                    # _pack4 repeats anyway.
                    rk = (_pack_rle(fm_np, self.pack4)
                          if self.rle and not skip_rle else None)
                    if sc_path is not None and not skip_rle:
                        # persist the encoding OR the negative result —
                        # an incompressible chunk must not re-pay the
                        # encode attempt every cold round
                        self._sidecar_save(sc_path, fp, rk)
                if rk is not None:
                    plen, pval, cnts = rk
                    fm_dev = _unpack_rle(
                        jnp.asarray(plen), jnp.asarray(pval),
                        jnp.asarray(cnts), c=c)
                    bytes_streamed += (plen.nbytes + pval.nbytes
                                       + cnts.nbytes)
                    chunks_rle += 1
                elif self.pack4 and (pk := _pack4(fm_np)) is not None:
                    packed, er, ec, ev = pk
                    fm_dev = _unpack4(
                        jnp.asarray(packed), self.graph.n,
                        jnp.asarray(er), jnp.asarray(ec),
                        jnp.asarray(ev))
                    bytes_streamed += (packed.nbytes + er.nbytes
                                       + ec.nbytes + ev.nbytes)
                    chunks_packed += 1
                else:
                    fm_dev = jnp.asarray(fm_np)
                    bytes_streamed += fm_np.nbytes
                bytes_raw += c * self.graph.n
                self._cache_put(key, fm_dev)
            lo, hi = bounds[ci], bounds[ci + 1]
            q_idx = q_by_chunk[lo:hi]
            # order by expected walk length so the kernel's bucketed
            # while_loops exit early (same trick as CPDOracle.route)
            est = length_estimate(self.graph, s_all[q_idx], t_all[q_idx])
            q_idx = q_idx[np.argsort(est, kind="stable")]
            rows_l = np.zeros(qp_all, np.int32)
            s_l = np.zeros(qp_all, np.int32)
            t_l = np.zeros(qp_all, np.int32)
            valid = np.zeros(qp_all, bool)
            rows_l[:len(q_idx)] = q_row[q_idx]
            s_l[:len(q_idx)] = s_all[q_idx]
            t_l[:len(q_idx)] = t_all[q_idx]
            valid[:len(q_idx)] = True
            dev = [fm_dev] + [jnp.asarray(a)
                              for a in (rows_l, s_l, t_l, valid)]
            return dev, q_idx

        # The pipeline is the XLA stream itself: uploads and walk
        # dispatches only ENQUEUE (async), so while the device DMAs and
        # walks chunk k the host is already gathering chunk k+1 — no
        # explicit prefetch thread (it would buy nothing that the async
        # stream does not already give).
        #: in-flight chunks (inputs AND outputs) kept on device at once.
        #: Device residency is bounded by DEPTH in-flight chunks PLUS up
        #: to ``cache_bytes`` of LRU-cached fm chunks (cached chunks are
        #: NOT freed on drain — that is the point of the cache); size
        #: ``cache_bytes`` accordingly, or 0 to get pure
        #: DEPTH-bounded streaming back
        DEPTH = 4

        def drain(entries):
            """Fetch + scatter a batch of finished chunks (one host
            round trip for however many are handed in)."""
            host = jax.device_get([o for _, o in entries])
            for (q_idx, _), got in zip(entries, host):
                if paths_k:
                    nodes, moves = got
                    out_nodes[q_idx] = nodes[:len(q_idx)]
                    out_p[q_idx] = moves[:len(q_idx)]
                    continue
                cost, plen, fin = got
                if n_multi:
                    out_c[:, q_idx] = cost[:, :len(q_idx)]
                else:
                    out_c[q_idx] = cost[:len(q_idx)]
                out_p[q_idx] = plen[:len(q_idx)]
                out_f[q_idx] = fin[:len(q_idx)]

        pending = []          # (q_idx, device result triple) per chunk
        for ci in range(n_chunks):
            (fm_d, rows_d, s_d, t_d, v_d), q_idx = prep(ci)
            if paths_k:
                outs = extract_paths(self.dg, fm_d, rows_d, s_d, t_d,
                                     k=paths_k)
            elif n_multi:
                outs = table_search_multi(
                    self.dg, fm_d, rows_d, s_d, t_d, w_pads_multi,
                    valid=v_d, max_steps=max_steps)
            else:
                outs = table_search_batch(
                    self.dg, fm_d, rows_d, s_d, t_d, w_pad,
                    valid=v_d, k_moves=k_moves, max_steps=max_steps)
            pending.append((q_idx, outs))
            if len(pending) >= DEPTH:
                drain(pending[:1])
                pending = pending[1:]
        # remaining chunks drain in ONE deferred host fetch (each
        # separate fetch pays a fixed device->host round trip)
        drain(pending)
        self.last_stats = {
            "n_queries": nq,
            "distinct_targets": int(len(uniq_t)),
            "row_chunks": n_chunks,
            # wire bytes actually uploaded (packed when pack4);
            # bytes_raw = the unpacked fm bytes those chunks represent,
            # so artifacts stay comparable across packing modes
            "bytes_streamed": int(bytes_streamed),
            "bytes_raw": int(bytes_raw),
            # packing that actually RAN, not just the enabled flag
            # (chunks can individually fall back when too many entries
            # escape)
            "pack4": self.pack4,
            "rle": self.rle,
            "chunks_packed": chunks_packed,
            "chunks_rle": chunks_rle,
            "sidecar_hits": sidecar_hits,
            "cache_hits": cache_hits,
            "cache_misses": cache_misses,
            "mode": "range" if range_mode else "compacted",
        }
        if paths_k:
            return out_nodes, out_p
        return out_c, out_p, out_f
