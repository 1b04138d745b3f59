"""Resident query server: the framework's ``fifo_auto``.

Behavior parity with reference C3 (SURVEY.md §2.2): on start, load the
graph, the first diff, and this worker's CPD shard; create the command FIFO
``/tmp/worker<wid>.fifo`` and block on it. Per request: parse the 2-line
config (JSON knobs + ``queryfile answerfifo difffile``), read the query
file, answer the batch, write ONE CSV stats line to the answer FIFO. Stays
resident across requests.

Extensions over the reference:

* a ``__DOS_STOP__`` line on the command FIFO shuts the server down cleanly
  (the reference can only be killed via tmux);
* errors answer the FIFO with an all-zero failure row instead of leaving the
  head blocked forever on ``cat <answer>``;
* launched as ``python -m distributed_oracle_search_tpu.worker.server -c
  conf.json --workerid N`` (by ``cli.make_fifos`` or by hand).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading

import numpy as np

from ..data.graph import Graph
from ..integrity.fingerprint import answer_fingerprint
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..parallel.partition import DistributionController
from ..testing import faults
from ..transport.wire import (
    HealthStatus, PING_TOKEN, Request, StatsRow, paths_file_for,
    read_query_file, results_file_for, write_paths_file,
    write_results_file,
)
from ..transport.fifo import command_fifo_path
from ..utils.config import ClusterConfig
from ..utils.env import env_cast
from ..utils.locks import OrderedLock
from ..utils.log import get_logger, set_verbosity, set_worker_id
from .engine import ShardEngine

log = get_logger(__name__)

STOP_TOKEN = "__DOS_STOP__"

# serve-loop health counters, declared at import so a snapshot shows the
# failure paths at zero even when they never fired (the reference had no
# visibility into any of these — frames and replies just vanished)
M_FRAMES = obs_metrics.counter(
    "server_frames_received_total", "frame starts seen on the command FIFO")
M_MALFORMED = obs_metrics.counter(
    "server_frames_malformed_total",
    "stray non-frame lines + undecodable 2-line requests")
M_HALF = obs_metrics.counter(
    "server_frames_half_total",
    "frames whose second line never arrived (timeout or config-only)")
M_BATCH_FAIL = obs_metrics.counter(
    "server_batches_failed_total", "engine exceptions answered with FAIL")
M_REPLIES = obs_metrics.counter(
    "server_replies_sent_total", "stats lines written to answer FIFOs")
M_DROPPED = obs_metrics.counter(
    "server_replies_dropped_total",
    "replies dropped: no reader within the deadline, or reader vanished")
M_REPLY_WAIT = obs_metrics.histogram(
    "server_reply_open_wait_seconds",
    "time a reply waited for the head to open its answer-FIFO reader")
M_PINGS = obs_metrics.counter(
    "server_pings_answered_total",
    "__DOS_PING__ control frames answered with a health line")
M_PING_DROPS = obs_metrics.counter(
    "server_ping_replies_dropped_total",
    "health replies dropped (prober gone) — kept separate from "
    "server_replies_dropped_total so data-plane drop alerts stay clean")
M_REPLICA_BATCHES = obs_metrics.counter(
    "server_replica_batches_total",
    "batches answered from a hosted REPLICA shard (failover/hedge "
    "traffic re-routed off the shard's primary)")
M_STALE_EPOCH = obs_metrics.counter(
    "server_stale_epoch_total",
    "batches refused with STALE_EPOCH: the request was routed under a "
    "NEWER partition-table epoch than this worker has, even after a "
    "membership refresh")
M_STALE_DIFF = obs_metrics.counter(
    "server_stale_diff_total",
    "batches refused with STALE_DIFF: the request named a fused diff "
    "from a NEWER traffic epoch than this worker's segment stream "
    "shows, even after a refresh")
G_RPC_CONNS = obs_metrics.gauge(
    "rpc_server_connections",
    "live client connections on this worker's RPC accept loop")
M_RPC_BATCHES = obs_metrics.counter(
    "rpc_server_batches_total",
    "batches answered over the socket transport (the RPC twin of "
    "server_replies_sent_total)")
M_RPC_DROPPED = obs_metrics.counter(
    "rpc_server_replies_dropped_total",
    "RPC replies dropped (drop-reply fault, or the client vanished "
    "before the reply frame)")
M_RPC_MALFORMED = obs_metrics.counter(
    "rpc_server_frames_malformed_total",
    "request frames whose config was undecodable (answered FAIL, "
    "never a wedge) — the socket twin of server_frames_malformed_total")
M_L2_HITS = obs_metrics.counter(
    "worker_l2_hits_total",
    "queries answered from the shard-owner L2 cache before the kernel")
M_L2_MISSES = obs_metrics.counter(
    "worker_l2_misses_total",
    "L2 lookups that fell through to the kernel")
M_L2_ADMIT_DENIED = obs_metrics.counter(
    "gateway_l2_admit_denied_total",
    "L2 inserts withheld by the second-hit admission doorkeeper "
    "(DOS_GATEWAY_L2_ADMIT=second-hit): first-miss keys only mark the "
    "ghost list, one-hit wonders never churn the byte budget")


class FifoServer:
    def __init__(self, conf: ClusterConfig, wid: int,
                 command_fifo: str | None = None,
                 alg: str = "table-search",
                 traffic_dir: str | None = None):
        from ..parallel import membership

        self.conf = conf
        self.wid = wid
        self.alg = alg
        #: live-traffic gate (``--traffic-dir``): a gate-only epoch
        #: manager over the shared segment stream — it never
        #: materializes fused files (the head did), it only tracks the
        #: stream's epoch so a request stamped with a NEWER diff epoch
        #: triggers a refresh-then-refuse instead of a failed open() on
        #: a fused file this worker's NFS view has not seen yet
        self.traffic = None
        if traffic_dir:
            from ..traffic import DiffEpochManager

            self.traffic = DiffEpochManager(traffic_dir,
                                            materialize=False)
            self.traffic.refresh()
        #: shard-owner L2 result cache (gateway tier, ``DOS_GATEWAY_
        #: L2_BYTES``): hot (s, t) entries answered BEFORE the kernel,
        #: keyed like the frontend L1 (diff path + knob fingerprint +
        #: both epochs) so fleet cache capacity scales with workers.
        #: Default 0 keeps pre-gateway workers byte-identical.
        from ..gateway.config import GatewayConfig
        from ..serving.cache import ResultCache

        gconf = GatewayConfig.from_env()
        self.l2 = ResultCache(gconf.l2_bytes)
        #: L2 admission policy (``DOS_GATEWAY_L2_ADMIT``): ``all``
        #: inserts every miss (byte-identical pre-HA behavior);
        #: ``second-hit`` keeps a ghost list of once-missed keys and
        #: admits only on the second miss, so one-hit-wonder queries
        #: cannot churn the byte budget
        self._l2_admit = gconf.l2_admit
        self._l2_seen: collections.OrderedDict = collections.OrderedDict()
        self._l2_seen_lock = OrderedLock("worker.FifoServer.l2_admit")
        if self.l2.enabled and self.traffic is not None:
            # scoped invalidation LOCAL to the shard owning the updated
            # edges: the gate-only epoch manager still computes each
            # swap's affected-edge delta, so the L2 re-keys its
            # provably-safe survivors exactly like the head's L1 did
            self._l2_prev = self.traffic.active()[:2]
            self.traffic.on_swap = self._l2_on_swap
        self.command_fifo = command_fifo or command_fifo_path(wid)
        self.graph = Graph.from_xy(conf.xy_file)
        self.dc = DistributionController(
            conf.partmethod, conf.partkey, conf.maxworker, self.graph.n,
            replication=conf.effective_replication())
        # elastic membership: the durable assignment (epoch + shard
        # owners) next to the index overrides the conf's static
        # identity — absent for a pre-elastic fleet (epoch 0)
        self._membership_state = membership.load_state(conf.outdir)
        if self._membership_state is not None:
            self.dc = membership.apply_state(self.dc,
                                             self._membership_state)
        self.epoch = self.dc.epoch
        #: lazily-loaded engines for the REPLICA shards this worker
        #: hosts (rank 1..R-1): failover traffic pays the replica load
        #: on first use, never at startup
        self._replica_engines: dict[int, ShardEngine] = {}
        # the eager primary engine serves the first shard this worker
        # OWNS (identity assignment: its own wid — today's behavior).
        # A fresh joiner owns nothing until its first epoch commits; it
        # starts engine-less and loads adopted shards lazily through
        # engine_for_shard, so join really is drain-free
        own = next((s for s in range(self.dc.maxworker)
                    if self.dc.owner_of(s) == wid), None)
        self.engine: ShardEngine | None = None
        if own is not None:
            self.engine = ShardEngine(self.graph, self.dc, wid,
                                      conf.outdir, alg=alg, shard=own)
            self._replica_engines[own] = self.engine
            # preload the first diff's weights like the reference
            # server does (make_fifos.py:18 loads only diffs[0])
            if conf.diffs:
                self.engine._weights_for(conf.diffs[0], no_cache=False)
        else:
            log.info("worker %d owns no shard at epoch %d (fresh "
                     "joiner); engines load lazily on adoption "
                     "traffic", wid, self.epoch)
        #: serializes engine answers across the FIFO and RPC serve
        #: loops (one ShardEngine, two transports over it)
        self._answer_lock = OrderedLock("worker.FifoServer.answer")

    @property
    def answer_lock(self) -> OrderedLock:
        """The cross-transport answer mutex; created lazily so bare
        test servers that skip ``__init__`` still serve."""
        lock = getattr(self, "_answer_lock", None)
        if lock is None:
            lock = self._answer_lock = OrderedLock(
                "worker.FifoServer.answer")
        return lock

    def engine_for_shard(self, shard: int) -> ShardEngine:
        """The engine serving ``shard``'s rows — the primary engine for
        an owned shard, a lazily-created replica engine for shards whose
        replica this worker hosts (or that it is mid-ADOPTING during a
        membership migration window), and a routing-invariant error for
        anything else (the engine's own check would catch it, but this
        diagnostic names the replica map)."""
        from ..parallel import membership

        eng = self._replica_engines.get(shard)
        if eng is None:
            def _hosted():
                return membership.hosted_shards(
                    getattr(self, "_membership_state", None), self.dc,
                    self.wid)

            hosted = _hosted()
            if shard not in hosted:
                # before refusing, re-read membership: a migration
                # WINDOW opens without an epoch bump, so a worker
                # started before `begin` only learns it is the adopter
                # when dual-read traffic actually lands here
                self._refresh_membership()
                hosted = _hosted()
            if shard not in hosted:
                raise ValueError(
                    f"worker {self.wid} hosts no replica of shard "
                    f"{shard} (hosted: {sorted(hosted)})"
                    " — routing invariant violated")
            log.info("worker %d: loading shard %d for failover/"
                     "adoption traffic", self.wid, shard)
            try:
                rank = self.dc.replica_rank(shard, self.wid)
            except ValueError:
                # mid-adoption: not in the shard's replica chain yet —
                # serve the primary block set the catch-up verified
                rank = 0
            eng = ShardEngine(self.graph, self.dc, self.wid,
                              self.conf.outdir, alg=self.alg,
                              shard=shard, replica=rank)
            self._replica_engines[shard] = eng
        return eng

    # ------------------------------------------------------------ serving
    def _ensure_fifo(self) -> None:
        if os.path.exists(self.command_fifo):
            os.remove(self.command_fifo)
        os.mkfifo(self.command_fifo)

    def handle(self, req: Request) -> StatsRow:
        if req.config.trace_id:
            # wire extension (obs.trace): the head stamped this batch
            # with a trace id — capture our spans under it and ship them
            # back as a sidecar next to the query file, like .paths
            with obs_trace.capture(req.config.trace_id) as cap:
                stats = self._handle(req)
            try:
                obs_trace.write_events(
                    obs_trace.trace_sidecar_for(req.queryfile),
                    cap.events)
            except OSError as e:
                log.error("cannot write trace sidecar for %s: %s",
                          req.queryfile, e)
            return stats
        return self._handle(req)

    def _handle(self, req: Request) -> StatsRow:
        with obs_trace.span("worker.receive", wid=self.wid,
                            queryfile=req.queryfile):
            queries = read_query_file(req.queryfile)
        cost, plen, fin, stats, paths = self.answer_queries(
            queries, req.config, req.difffile)
        if paths is not None:
            # extraction rides the shared dir, not the stats FIFO (wire
            # extension: transport.wire.paths_file_for)
            write_paths_file(paths_file_for(req.queryfile), *paths)
        if req.config.results and (len(queries)
                                   or self.engine is not None):
            # per-query answers for the online serving frontend — same
            # shared-dir sidecar pattern as .paths (wire extension:
            # transport.wire.results_file_for). The guard preserves the
            # pre-refactor shape exactly: an engine-less empty batch
            # answered the empty row without materializing a sidecar
            fp = None
            if req.config.answer_fp:
                # fingerprint at answer birth (integrity wire
                # extension); the corrupt-answer fault fires AFTER, so
                # the head's verifier is what must catch the rot
                fp = answer_fingerprint(cost, plen, fin)
                if faults.inject("corrupt-answer", self.wid) is not None:
                    cost = np.array(cost, np.int64, copy=True)
                    if len(cost):
                        cost[0] ^= 1
            write_results_file(results_file_for(req.queryfile),
                               cost, plen, fin, fp=fp)
        return stats

    def answer_queries(self, queries: np.ndarray, config, difffile: str):
        """The file-less core of one batch — shard-aware engine
        selection, the engine answer, captured path prefixes — shared
        by the FIFO serve loop (which wraps it in query-file/sidecar
        IO) and the RPC serve loop (which ships the same outputs as
        reply-frame payload segments). Returns ``(cost, plen, fin,
        stats, paths)`` with ``paths = engine.last_paths`` or None."""
        engine = self.engine
        if len(queries):
            # shard-aware dispatch: a failover/hedge batch targets a
            # shard we host as a replica — or one we own/are adopting
            # under an elastic membership assignment — serve it from
            # that shard's engine instead of failing the primary's
            # routing invariant. The scan runs unconditionally (one
            # np.unique over the batch targets): it is also how a
            # worker started BEFORE a migration window discovers it is
            # the adopter (engine_for_shard refreshes membership on a
            # hosted miss), and a genuine misroute still fails with
            # the routing-invariant diagnostic, now naming the full
            # hosted-shard map.
            shards = np.unique(self.dc.worker_of(queries[:, 1]))
            if len(shards) == 1 and (engine is None
                                     or int(shards[0]) != engine.shard):
                engine = self.engine_for_shard(int(shards[0]))
                if (engine is not self.engine
                        and int(self.dc.owner_of(int(shards[0])))
                        != self.wid):
                    # count only genuinely re-routed traffic: after a
                    # leave consolidates two OWNED shards onto this
                    # worker, the non-eager one's batches are
                    # authoritative, not failover
                    M_REPLICA_BATCHES.inc()
        if engine is None:
            if len(queries):
                # a fresh joiner got a batch it has no engine for (the
                # single-shard case resolved above would have raised or
                # loaded one; this is a multi-shard misroute): FAIL it
                # loudly so failover walks on — an ok=True zero row
                # would silently swallow the queries
                raise ValueError(
                    f"worker {self.wid} owns no shard and the batch "
                    f"spans shards "
                    f"{np.unique(self.dc.worker_of(queries[:, 1])).tolist()}"
                    " — routing invariant violated")
            # an empty batch needs no engine: answer the empty row
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, bool), StatsRow(), None)
        l2 = getattr(self, "l2", None)
        if (l2 is not None and l2.enabled
                and not (getattr(config, "extract", False)
                         and getattr(config, "k_moves", 0) > 0)):
            # extraction batches need the REAL per-move prefixes on the
            # paths sidecar; everything else can short-circuit
            return self._answer_l2(engine, queries, config, difffile)
        cost, plen, fin, stats = engine.answer(queries, config,
                                               difffile)
        return cost, plen, fin, stats, engine.last_paths

    def _answer_l2(self, engine, queries: np.ndarray, config,
                   difffile: str):
        """The two-level cache plane's worker half: per-query L2
        lookups before the kernel, the kernel only over the misses,
        results merged back in query order. Keys mirror the frontend
        L1 (diff path, knob fingerprint, membership epoch, diff epoch)
        so an entry can never outlive the state that computed it; for
        sig-requesting callers a hit fabricates its paths row from the
        stored signature (sentinel ``moves=-1`` when it cannot — the
        frontend then conservatively treats the entry sig-less)."""
        from ..serving.cache import knob_fingerprint

        l2 = self.l2
        fp = knob_fingerprint(config)
        epoch = int(getattr(self, "epoch", 0))
        depoch = int(getattr(config, "diff_epoch", 0) or 0)
        q = np.asarray(queries)
        n = len(q)
        keys = [(int(q[i, 0]), int(q[i, 1]), str(difffile), fp,
                 epoch, depoch) for i in range(n)]
        sig_k = int(getattr(config, "sig_k", 0) or 0)
        width = sig_k + 1 if sig_k > 0 else 0
        cost = np.zeros(n, np.int64)
        plen = np.zeros(n, np.int64)
        fin = np.zeros(n, bool)
        nodes = np.zeros((n, width), np.int64) if width else None
        moves = np.full(n, -1, np.int64) if width else None
        miss_idx = []
        for i, key in enumerate(keys):
            hit = l2.get_with_sig(key)
            if hit is None:
                miss_idx.append(i)
                continue
            (c, p, f), sig = hit
            cost[i], plen[i], fin[i] = int(c), int(p), bool(f)
            if (width and sig is not None and 0 < len(sig) <= width
                    and len(sig) - 1 == int(p)):
                srt = sorted(sig)
                nodes[i, :len(srt)] = srt
                moves[i] = len(srt) - 1
        M_L2_HITS.inc(n - len(miss_idx))
        M_L2_MISSES.inc(len(miss_idx))
        stats = StatsRow()
        if miss_idx:
            idx = np.asarray(miss_idx)
            c2, p2, f2, stats = engine.answer(
                np.ascontiguousarray(q[idx]), config, difffile)
            cost[idx], plen[idx], fin[idx] = c2, p2, f2
            lp = engine.last_paths
            lp_ok = (width and lp is not None
                     and lp[0].shape[1] == width)
            if lp_ok:
                nodes[idx] = lp[0]
                moves[idx] = lp[1]
            for j, i in enumerate(miss_idx):
                sig = None
                if lp_ok and int(lp[1][j]) == int(p2[j]):
                    sig = frozenset(
                        int(x) for x in lp[0][j, :int(lp[1][j]) + 1])
                if self._l2_admit_key(keys[i]):
                    l2.put(keys[i],
                           (int(c2[j]), int(p2[j]), bool(f2[j])), sig)
        paths = (nodes, moves) if width else None
        return cost, plen, fin, stats, paths

    def _l2_admit_key(self, key) -> bool:
        """Admission doorkeeper for one missed key. ``all`` admits
        everything; ``second-hit`` admits only a key whose FIRST miss
        already marked the ghost list (bounded FIFO of key hashes —
        a ghost entry costs a set slot, not a cached value's bytes)."""
        if self._l2_admit != "second-hit":
            return True
        cap = max(1024, int(self.l2.max_bytes) // 256)
        with self._l2_seen_lock:
            if self._l2_seen.pop(key, None) is not None:
                return True
            self._l2_seen[key] = True
            while len(self._l2_seen) > cap:
                self._l2_seen.popitem(last=False)
        M_L2_ADMIT_DENIED.inc()
        return False

    def _l2_on_swap(self, epoch: int, difffile: str,
                    affected) -> None:
        """Diff-epoch swap hook (gate-only epoch manager): scoped
        invalidation of this shard's L2 — entries whose cached walk
        provably avoids every updated edge re-key to the new fusion,
        the rest drop. Runs on whichever thread refreshed the stream,
        outside the manager's lock."""
        old_diff, old_epoch = "", 0
        prev = getattr(self, "_l2_prev", None)
        if prev is not None:
            old_epoch, old_diff = int(prev[0]), str(prev[1])
        self._l2_prev = (epoch, difffile)
        dropped, kept, reason = self.l2.invalidate_scoped(
            affected, difffile, epoch,
            max_edges=self.traffic.scoped_max,
            old_diff=old_diff, old_depoch=old_epoch)
        log.info("worker %d L2 swap epoch %d -> %d: %d dropped (%s), "
                 "%d re-keyed", self.wid, old_epoch, epoch, dropped,
                 reason, kept)

    def serve_forever(self) -> None:
        """Framed request loop over a PERSISTENT command-FIFO read session.

        The reference documents a FIFO race (reference README.md:125-127)
        that a naive open-to-EOF session per request re-inherits: if
        writer B opens the FIFO before the server sees writer A's EOF,
        B's request lands in the dying session and is silently dropped —
        B then blocks forever on its answer FIFO. So instead the server
        opens the FIFO once with ``O_RDWR`` (its own write end guarantees
        ``readline`` never sees EOF, only blocks) and parses requests
        frame-by-frame: exactly two newline-terminated lines each.
        Back-to-back writers simply queue in the pipe buffer — a request
        under ``PIPE_BUF`` (4 KiB on Linux, far above any real request)
        is written atomically, so frames can never interleave.
        """
        import time as _time

        self._ensure_fifo()
        set_worker_id(self.wid)      # tag this serve thread's log records
        log.info("worker %d serving on %s", self.wid, self.command_fifo)
        # liveness state answered to __DOS_PING__ control frames (set
        # here, not __init__: bare test servers skip __init__, and the
        # uptime clock should start when serving does)
        self._t_start = _time.monotonic()
        self._batches = 0
        self._batch_failures = 0
        self._last_error = ""
        fd = os.open(self.command_fifo, os.O_RDWR)
        self._rdbuf = b""
        try:
            while True:
                line1 = self._next_line(fd)
                if STOP_TOKEN in line1:
                    log.info("worker %d: stop requested", self.wid)
                    return
                if not line1.strip():
                    continue
                if line1.lstrip().startswith(PING_TOKEN):
                    # single-line control frame: never counts as a data
                    # frame, never touches the engine
                    self._answer_ping(line1)
                    continue
                M_FRAMES.inc()
                if not line1.lstrip().startswith("{"):
                    # frame starts are self-identifying: a config line is
                    # always a JSON object, a paths line never is. A stray
                    # non-JSON line is garbage — handle it standalone so
                    # it can NEVER pair with (and eat) the next writer's
                    # config line; best-effort FAIL any FIFO it names
                    log.error("stray non-frame line: %r", line1)
                    M_MALFORMED.inc()
                    self._answer_malformed(line1)
                    continue
                # a legit writer ships both lines in ONE atomic write, so
                # line 2 is already in the pipe; bound the wait so a
                # config-only garbage frame cannot desync the stream
                line2 = self._next_line(fd, timeout=self.FRAME_TIMEOUT_S)
                if line2 is None:
                    log.error("half frame (no line 2 within %.1fs): %r",
                              self.FRAME_TIMEOUT_S, line1)
                    M_HALF.inc()
                    continue
                if STOP_TOKEN in line2:
                    # a stop chasing a truncated 1-line request must
                    # still win: never strand the shutdown token
                    log.info("worker %d: stop requested", self.wid)
                    return
                if line2.lstrip().startswith("{"):
                    # a config line where the paths line belongs: the
                    # previous writer truncated. Push it back to start the
                    # next frame instead of corrupting two requests
                    log.error("config-only half frame: %r", line1)
                    M_HALF.inc()
                    self._rdbuf = line2.encode() + self._rdbuf
                    continue
                text = line1 + line2
                try:
                    req = Request.decode(text)
                except ValueError as e:
                    log.error("bad request: %s", e)
                    M_MALFORMED.inc()
                    self._answer_malformed(text)
                    continue
                stale = (self._epoch_gate(req.config)
                         or self._traffic_gate(req.config))
                if stale is not None:
                    # version-gated refusal: the head routed this batch
                    # under a NEWER partition table than we can see —
                    # answer the sentinel so failover walks on instead
                    # of us serving rows we may no longer own
                    self._reply(req.answerfifo,
                                stale.encode_wire() + "\n")
                    continue
                kill = faults.inject("kill-mid-batch", wid=self.wid)
                if kill is not None:
                    # the injected analog of a worker crash between
                    # reading a request and answering it — the exact
                    # failure that wedges the reference head forever
                    log.error("fault: worker %d dying mid-batch",
                              self.wid)
                    if kill.mode == "exit":
                        os._exit(faults.KILL_EXIT_CODE)
                    return  # mode=raise: in-thread server dies quietly
                try:
                    if faults.inject("crash-engine",
                                     wid=self.wid) is not None:
                        raise RuntimeError("injected fault: crash-engine")
                    with self.answer_lock:
                        stats = self.handle(req)
                    self._batches += 1
                except Exception as e:  # noqa: BLE001 — never leave
                    # the head blocked on `cat answer`; send a failure
                    log.exception("batch failed: %s", e)
                    M_BATCH_FAIL.inc()
                    self._batches += 1
                    self._batch_failures += 1
                    self._last_error = f"{type(e).__name__}: {e}"
                    stats = StatsRow.failed()
                delay = faults.inject("delay", wid=self.wid)
                if delay is not None:
                    log.warning("fault: delaying reply %.2fs", delay.delay)
                    _time.sleep(delay.delay)
                if faults.inject("drop-reply", wid=self.wid) is not None:
                    log.error("fault: dropping reply to %s",
                              req.answerfifo)
                    M_DROPPED.inc()
                    continue
                self._reply(req.answerfifo, stats.encode_wire() + "\n")
        finally:
            os.close(fd)
            if os.path.exists(self.command_fifo):
                os.remove(self.command_fifo)

    #: bound on the gap between a frame's two lines (one atomic writer
    #: write puts both in the pipe together; only garbage arrives alone)
    FRAME_TIMEOUT_S = 2.0

    def _next_line(self, fd: int, timeout: float | None = None):
        """Next newline-terminated line off the persistent FIFO fd (own
        buffering — a buffered file object would hide pipe data from
        ``select``). ``timeout`` bounds the TOTAL wait (None = forever):
        the deadline is absolute, so a byte-trickling writer that keeps
        waking ``select`` without ever completing a line cannot hold a
        half-frame wait open indefinitely. Returns None on timeout."""
        import select
        import time as _time

        deadline = (None if timeout is None
                    else _time.monotonic() + timeout)
        while True:
            nl = self._rdbuf.find(b"\n")
            if nl >= 0:
                line = self._rdbuf[:nl + 1]
                self._rdbuf = self._rdbuf[nl + 1:]
                return line.decode(errors="replace")
            if deadline is not None:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    return None
                ready, _, _ = select.select([fd], [], [], remaining)
                if not ready:
                    return None
            chunk = os.read(fd, 4096)
            if not chunk:       # cannot happen with our own O_RDWR write
                _time.sleep(0.01)  # defensive: never spin
            self._rdbuf += chunk

    @property
    def reply_deadline_s(self) -> float:
        """How long to wait for the head to open its answer-FIFO reader.
        Read lazily (not at import) so tests/monkeypatched env work; a
        malformed value falls back to the default instead of crashing."""
        v = env_cast("DOS_REPLY_DEADLINE_S", 30.0, float)
        # a zero/negative deadline would drop every reply whose reader
        # has not already opened — same guard as the native server's
        return v if v > 0 else 30.0

    def _reply(self, answerfifo: str, line: str,
               deadline_s: float | None = None,
               drop_counter=None) -> None:
        """Write the stats line without ever wedging the server: a
        blocking ``open(fifo, 'w')`` would hang forever if the head's
        ``cat <answer>`` was killed before opening its end. Non-blocking
        open with a bounded deadline (``deadline_s`` overrides the
        configured one); drop the reply (logged) if no reader appears.
        ``drop_counter`` overrides which counter books the drop (control
        frames must not pollute the data-plane drop alert)."""
        import errno
        import time as _time

        dropped = drop_counter if drop_counter is not None else M_DROPPED
        wait_s = (deadline_s if deadline_s is not None
                  else self.reply_deadline_s)
        t_wait0 = _time.monotonic()
        deadline = t_wait0 + wait_s
        fd = -1
        while fd < 0:
            try:
                fd = os.open(answerfifo, os.O_WRONLY | os.O_NONBLOCK)
            except OSError as e:
                if e.errno not in (errno.ENXIO, errno.ENOENT):
                    log.error("cannot open %s: %s", answerfifo, e)
                    dropped.inc()
                    return
                if _time.monotonic() > deadline:
                    log.error("no reader on %s within %.0fs; dropping "
                              "reply", answerfifo, wait_s)
                    dropped.inc()
                    return
                _time.sleep(0.05)
        M_REPLY_WAIT.observe(_time.monotonic() - t_wait0)
        try:
            # reader present: restore blocking mode for the write itself
            import fcntl
            fcntl.fcntl(fd, fcntl.F_SETFL,
                        fcntl.fcntl(fd, fcntl.F_GETFL) & ~os.O_NONBLOCK)
            os.write(fd, line.encode())
            M_REPLIES.inc()
        except OSError as e:
            # reader vanished between open and write (BrokenPipe):
            # drop the reply, never crash the serve loop
            log.error("reply to %s failed: %s", answerfifo, e)
            dropped.inc()
        finally:
            os.close(fd)

    #: reader-wait for best-effort malformed replies: a garbage frame's
    #: "answer FIFO" may be a stray path nobody reads, and the full
    #: reply deadline (default 30 s) would stall the single-threaded
    #: serve loop that long PER garbage frame
    MALFORMED_REPLY_DEADLINE_S = 2.0

    def _answer_malformed(self, text: str) -> None:
        """Best effort: find an answer-FIFO path among the tokens of a
        malformed request (any line — a stray paths line carries it in
        token 2, a full 2-line frame in line 2) and send the failure
        sentinel, so the head's ``cat <answer>`` never blocks forever."""
        import stat

        for line in text.strip("\n").split("\n"):
            for tok in line.split():
                try:
                    if stat.S_ISFIFO(os.stat(tok).st_mode):
                        self._reply(tok,
                                    StatsRow.failed().encode_wire() + "\n",
                                    deadline_s=self
                                    .MALFORMED_REPLY_DEADLINE_S)
                        return
                except OSError:
                    continue

    #: reader-wait for ping replies: the prober is already blocked on its
    #: answer FIFO when the ping lands, so a long wait only ever means
    #: the prober died — don't stall the serve loop for it
    PING_REPLY_DEADLINE_S = 5.0

    def _answer_ping(self, line: str) -> None:
        """Answer a ``__DOS_PING__ <answerfifo>`` control frame with one
        health JSON line (:class:`~..transport.wire.HealthStatus`)."""
        toks = line.split()
        if len(toks) < 2:
            log.error("ping frame names no answer FIFO: %r", line)
            return
        status = self._health_status()
        self._reply(toks[1], status.to_json() + "\n",
                    deadline_s=self.PING_REPLY_DEADLINE_S,
                    drop_counter=M_PING_DROPS)
        M_PINGS.inc()

    def stop_file(self) -> None:
        """Write the stop token into our own FIFO (for another process)."""
        stop_server(self.command_fifo)

    # -------------------------------------------------- membership gate
    def _epoch_gate(self, config) -> StatsRow | None:
        """The wire-compat version gate applied to routing state: a
        request stamped with a NEWER partition-table epoch than ours
        first triggers a membership refresh (the commit may simply not
        have been read yet — the normal case right after an epoch
        bump), and only if we are STILL older is it refused with the
        ``STALE_EPOCH`` sentinel. Requests from older epochs are always
        served (the dual-read window depends on it). Returns the
        refusal row, or None to proceed."""
        if faults.inject("stale-epoch-reply", wid=self.wid) is not None:
            # the injected analog of a worker whose membership state
            # is wedged behind the fleet: refuse even though our table
            # may be current, forcing the head's failover path
            log.error("fault: worker %d replying STALE_EPOCH", self.wid)
            M_STALE_EPOCH.inc()
            return StatsRow(ok=False, stale_epoch=True)
        req_epoch = int(getattr(config, "epoch", 0) or 0)
        if req_epoch <= getattr(self, "epoch", 0):
            return None
        self._refresh_membership()
        if req_epoch <= getattr(self, "epoch", 0):
            return None
        M_STALE_EPOCH.inc()
        log.warning("worker %d at epoch %d refusing batch from epoch "
                    "%d (membership state has no newer commit)",
                    self.wid, getattr(self, "epoch", 0), req_epoch)
        return StatsRow(ok=False, stale_epoch=True)

    def _traffic_gate(self, config) -> StatsRow | None:
        """The tolerate-older / gate-newer rule applied to the DIFF
        epoch (``RuntimeConfig.diff_epoch`` wire extension): a request
        fused at a NEWER traffic epoch than our segment stream shows
        first refreshes the stream (the segment may simply not have
        been polled yet — the normal case right after a swap), and only
        if we are STILL older refuses with the ``STALE_DIFF`` sentinel
        so the head fails over instead of this worker failing an open()
        on a not-yet-visible fused file. Requests from older diff
        epochs are always served (the spool's keep window holds their
        files). Workers without ``--traffic-dir`` never gate — the
        difffile on the wire is a concrete path they can read or fail
        loudly on."""
        traffic = getattr(self, "traffic", None)
        if traffic is None:
            return None
        req_depoch = int(getattr(config, "diff_epoch", 0) or 0)
        if req_depoch <= traffic.epoch:
            return None
        traffic.refresh()
        if req_depoch <= traffic.epoch:
            return None
        M_STALE_DIFF.inc()
        log.warning("worker %d at diff epoch %d refusing batch from "
                    "diff epoch %d (segment stream has no newer "
                    "segment)", self.wid, traffic.epoch, req_depoch)
        return StatsRow(ok=False, stale_diff=True)

    def _refresh_membership(self) -> None:
        """Re-read the durable membership state (epoch + owners +
        in-flight migration) and swap in a controller reflecting it.
        A same-epoch state still applies when its CONTENT changed —
        `begin` opens a migration window without bumping the epoch,
        and the adopter must see the window to host dual-read traffic.
        An older epoch never applies (a lagging reader must not roll
        routing back). Loaded engines keep serving — the node→shard
        map never changes, only ownership."""
        from ..parallel import membership

        if not hasattr(self, "conf"):       # bare test server
            return
        try:
            state = membership.load_state(self.conf.outdir)
        except ValueError as e:
            log.error("membership refresh failed: %s", e)
            return
        if state is None or state.epoch < getattr(self, "epoch", 0):
            return
        cur = getattr(self, "_membership_state", None)
        if cur is not None and state.to_dict() == cur.to_dict():
            return
        self._membership_state = state
        self.dc = membership.apply_state(self.dc, state)
        old_epoch = getattr(self, "epoch", 0)
        self.epoch = state.epoch
        l2 = getattr(self, "l2", None)
        if l2 is not None and l2.enabled and state.epoch != old_epoch:
            # old-epoch L2 keys are unreachable after a commit (the
            # epoch is in the key) — flush so the budget serves the
            # new assignment instead of pinning dead entries
            n = l2.invalidate()
            log.info("worker %d L2 flushed %d entries on epoch "
                     "%d -> %d", self.wid, n, old_epoch, state.epoch)
        log.info("worker %d refreshed membership (epoch %d%s)",
                 self.wid, self.epoch,
                 ", migration window open"
                 if state.migration is not None else "")

    # ----------------------------------------------------- obs endpoints
    def _health_status(self) -> HealthStatus:
        """One health truth for both probes: the ``__DOS_PING__``
        control frame and the ``/healthz`` endpoint serialize this
        same object."""
        import time as _time

        return HealthStatus(
            ok=True, wid=self.wid, pid=os.getpid(),
            uptime_s=_time.monotonic() - getattr(self, "_t_start", 0.0),
            batches=getattr(self, "_batches", 0),
            batch_failures=getattr(self, "_batch_failures", 0),
            dropped=int(M_DROPPED.value),
            last_error=getattr(self, "_last_error", ""),
        )

    def health(self) -> dict:
        """``/healthz`` payload — the same :class:`HealthStatus`
        a ``__DOS_PING__`` probe gets, minus the FIFO."""
        import dataclasses as _dc

        return _dc.asdict(self._health_status())

    def statusz(self) -> dict:
        """``/statusz`` section: serve-loop health plus what this worker
        actually hosts — its shard, any lazily-loaded replica engines
        (is failover traffic landing here?), and the build ledger's
        journaled-block count (how far a crash-resumed build got)."""
        from ..models.cpd import BuildLedger

        out = dict(self.health())
        out["alg"] = self.alg
        out["command_fifo"] = self.command_fifo
        out["shard"] = self.wid
        # worker mesh shape: how many local devices this worker's
        # engine drives (1 = legacy single-device). Older workers omit
        # the key; `dos-obs top` renders a blank, never a crash.
        eng = self.engine
        out["mesh"] = {
            "devices": int(getattr(eng, "n_lanes", 1) or 1),
            "axis": "lane",
        }
        # compressed residency: what DOS_CPD_RESIDENT resolved to for
        # this shard and the device bytes the table occupies (older
        # workers omit the key; `dos-obs top` renders a blank)
        out["resident"] = {
            "codec": str(getattr(eng, "resident_codec", "raw")),
            "bytes": int(getattr(eng, "resident_bytes", 0) or 0),
        }
        out["replica_shards_loaded"] = sorted(
            s for s in self._replica_engines if s != self.wid)
        if self.dc.replication > 1:
            out["replica_shards_hosted"] = sorted(
                int(s) for s in self.dc.replica_shards(self.wid))
        # elastic membership: which table version this worker serves
        # under, and (when a reconfiguration is in flight) the window —
        # a pre-elastic worker simply omits both keys, and consumers
        # (`dos-obs top`) render blanks for a missing key, never crash
        out["epoch"] = int(getattr(self, "epoch", 0))
        # live-traffic column: present only when this worker gates the
        # diff stream (`dos-obs top` renders a blank otherwise — the
        # same mixed-schema tolerance as the membership columns)
        traffic = getattr(self, "traffic", None)
        if traffic is not None:
            out["diff_epoch"] = int(traffic.epoch)
        # gateway cache plane: present only when the shard-owner L2 is
        # enabled (pre-gateway fleets omit the key; `dos-obs top`
        # renders blanks, never a crash)
        l2 = getattr(self, "l2", None)
        if l2 is not None and l2.enabled:
            out["l2"] = {
                "entries": len(l2),
                "max_bytes": l2.max_bytes,
                "hits": int(l2.hits),
                "misses": int(l2.misses),
                "hit_rate": round(l2.hit_rate(), 4),
                "admit": str(getattr(self, "_l2_admit", "all")),
            }
        state = getattr(self, "_membership_state", None)
        if state is not None and state.migration is not None:
            out["migration"] = dict(state.migration)
        # streaming-transport column: present only when the RPC accept
        # loop is serving (`dos-obs top` renders blanks for pre-RPC
        # workers — the same mixed-schema tolerance as the rest)
        rpc_loop = getattr(self, "rpc_loop", None)
        if rpc_loop is not None:
            out["transport"] = rpc_loop.statusz()
        # telemetry column: present only when this worker publishes
        # ticks (pre-telemetry workers omit it; consumers blank it)
        publisher = getattr(self, "telemetry", None)
        if publisher is not None:
            out["telemetry"] = publisher.statusz()
        try:
            out["build_ledger_blocks"] = len(
                BuildLedger(self.conf.outdir, self.wid).entries())
        except (OSError, ValueError):
            out["build_ledger_blocks"] = 0
        return out


class RpcServeLoop:
    """The socket accept loop beside the FIFO serve loop.

    One :class:`FifoServer` (engine, membership/diff epoch gates,
    health state, fault-injection points) served over persistent
    connections: length-prefixed frames (:mod:`..transport.frames`),
    multiplexed by frame id, queries/results as raw ndarray payload
    segments instead of shared-dir files. Each connection gets a
    ``hello`` frame advertising the credit window; requests past the
    window answer an explicit ``busy`` frame instead of queueing into a
    timeout. ``ping`` frames answer the same
    :class:`~..transport.wire.HealthStatus` the ``__DOS_PING__``
    control frame does.

    Every fault point of the FIFO loop fires here too — ``crash-engine``
    (answered FAIL), ``delay``, ``drop-reply`` (reply frame withheld;
    the client times out retryable), ``kill-mid-batch`` (``mode=exit``
    hard-exits; ``mode=raise`` tears the transport down, the in-thread
    test analog of a crash) — so chaos drills exercise the socket lane
    through the same ``DOS_FAULTS`` specs."""

    def __init__(self, server: FifoServer, socket_path: str | None = None,
                 tcp_port: int | None = None, credit: int | None = None):
        from ..transport import rpc as rpc_transport

        self.fs = server
        self.socket_path = (socket_path if socket_path is not None
                            else rpc_transport.rpc_socket_path(server.wid))
        self.tcp_port = tcp_port
        self.credit = (credit if credit is not None
                       else max(1, env_cast("DOS_RPC_CREDIT", 8, int)))
        self._listener = None
        self._threads: list = []
        self._conns: list = []
        self._writers: dict = {}    # sock -> FrameWriter (broadcasts)
        self._stop = threading.Event()
        self._lock = OrderedLock("worker.RpcServeLoop")
        self._inflight = 0
        self._served = 0

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "RpcServeLoop":
        import socket as _socket
        import threading as _threading

        if self.tcp_port is not None:
            lst = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
            lst.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            lst.bind(("0.0.0.0", int(self.tcp_port)))
            self.endpoint = f"tcp:*:{lst.getsockname()[1]}"
        else:
            if os.path.exists(self.socket_path):
                os.remove(self.socket_path)
            lst = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
            lst.bind(self.socket_path)
            self.endpoint = f"unix:{self.socket_path}"
        lst.listen(16)
        self._listener = lst
        self.fs.rpc_loop = self     # the /statusz transport section
        t = _threading.Thread(target=self._accept_loop, daemon=True,
                              name=f"dos-rpc-accept-w{self.fs.wid}")
        self._threads.append(t)
        t.start()
        log.info("worker %d rpc serving on %s (credit %d)", self.fs.wid,
                 self.endpoint, self.credit)
        return self

    def stop(self, join_s: float = 5.0) -> None:
        self._stop.set()
        from ..transport.rpc import shutdown_close

        lst, self._listener = self._listener, None
        if lst is not None:
            shutdown_close(lst)
        with self._lock:
            conns, self._conns = list(self._conns), []
        for c in conns:
            shutdown_close(c)
        with self._lock:
            threads, self._threads = list(self._threads), []
        for t in threads:
            t.join(timeout=join_s)
        if self.tcp_port is None and os.path.exists(self.socket_path):
            try:
                os.remove(self.socket_path)
            except OSError as e:
                log.debug("rpc socket unlink failed: %s", e)

    # ------------------------------------------------------------ serving
    def _accept_loop(self) -> None:
        import threading as _threading

        while not self._stop.is_set():
            try:
                sock, _ = self._listener.accept()
            except (OSError, AttributeError):
                return      # listener closed by stop()
            with self._lock:
                self._conns.append(sock)
            G_RPC_CONNS.add(1)
            t = _threading.Thread(
                target=self._conn_loop, args=(sock,), daemon=True,
                name=f"dos-rpc-conn-w{self.fs.wid}")
            with self._lock:
                self._threads.append(t)
            t.start()

    def _conn_loop(self, sock) -> None:
        from ..transport import frames

        reader = frames.FrameReader(sock)
        writer = frames.FrameWriter(sock)
        with self._lock:
            self._writers[sock] = writer    # telemetry broadcast lane
        try:
            writer.send({"kind": "hello", "wid": self.fs.wid,
                         "credit": self.credit})
            while not self._stop.is_set():
                fr = reader.read()
                if fr is None:
                    return                  # clean client hangup
                if fr.kind == "ping":
                    self._answer_ping(fr, writer)
                elif fr.kind == "req":
                    if not self._serve_req(fr, writer):
                        return              # kill-mid-batch mode=raise
                else:
                    # unknown kinds are the schema-tolerance rule
                    # applied to frames: skip, never kill the session
                    log.warning("ignoring unknown rpc frame kind %r",
                                fr.kind)
        except frames.TransportError as e:
            log.warning("rpc connection to worker %d died: %s",
                        self.fs.wid, e)
        except frames.FrameSchemaError as e:
            log.error("rpc peer speaks a newer frame schema: %s", e)
        finally:
            from ..transport.rpc import shutdown_close
            shutdown_close(sock)
            me = threading.current_thread()
            with self._lock:
                self._writers.pop(sock, None)
                if sock in self._conns:
                    self._conns.remove(sock)
                # prune this handler from the join list: every breaker
                # probe opens a fresh connection, and a long-lived
                # worker must not accumulate dead Thread objects
                if me in self._threads:
                    self._threads.remove(me)
            G_RPC_CONNS.add(-1)

    def _answer_ping(self, fr, writer) -> None:
        from ..transport import frames

        status = self.fs._health_status()
        try:
            writer.send({"kind": "health", "id": fr.header.get("id"),
                         "status": json.loads(status.to_json())})
            M_PINGS.inc()
        except frames.TransportError as e:
            log.warning("rpc health reply failed: %s", e)
            M_PING_DROPS.inc()

    def _serve_req(self, fr, writer) -> bool:
        """Answer one ``req`` frame; False tears the transport down
        (the ``kill-mid-batch`` in-thread analog)."""
        import time as _time

        from ..transport import frames, rpc as rpc_transport
        from ..transport.wire import StatsRow as _StatsRow

        fs = self.fs
        fid = fr.header.get("id")
        with self._lock:
            busy = self._inflight >= self.credit
            if not busy:
                self._inflight += 1
        if busy:
            # explicit backpressure: the client books BUSY now instead
            # of discovering a saturated worker by timeout
            rpc_transport.M_BUSY.inc()
            try:
                writer.send({"kind": "busy", "id": fid})
            except frames.TransportError as e:
                log.warning("rpc busy reply failed: %s", e)
            return True
        try:
            try:
                rconf = rpc_transport.config_from_wire(
                    fr.header.get("config"))
                queries = (np.asarray(fr.arrays[0], np.int64)
                           .reshape(-1, 2) if fr.arrays
                           else np.zeros((0, 2), np.int64))
            except (ValueError, TypeError) as e:
                log.error("malformed rpc request: %s", e)
                M_RPC_MALFORMED.inc()
                self._reply(writer, {"kind": "rep", "id": fid,
                                     "stats": _StatsRow.failed()
                                     .encode_wire()})
                return True
            diff = str(fr.header.get("diff") or "-")
            stale = fs._epoch_gate(rconf) or fs._traffic_gate(rconf)
            if stale is not None:
                self._reply(writer, {"kind": "rep", "id": fid,
                                     "stats": stale.encode_wire()})
                return True
            kill = faults.inject("kill-mid-batch", wid=fs.wid)
            if kill is not None:
                log.error("fault: worker %d dying mid-batch (rpc)",
                          fs.wid)
                if kill.mode == "exit":
                    os._exit(faults.KILL_EXIT_CODE)
                # mode=raise: the in-thread server dies — stop
                # accepting, close the listener so new connects are
                # refused; the torn socket is the client's signal
                self._stop.set()
                lst, self._listener = self._listener, None
                if lst is not None:
                    rpc_transport.shutdown_close(lst)
                return False
            header = {"kind": "rep", "id": fid}
            arrays: list = []
            try:
                if faults.inject("crash-engine", wid=fs.wid) is not None:
                    raise RuntimeError("injected fault: crash-engine")
                cost, plen, fin, stats, paths = self._answer(
                    rconf, queries, diff, header)
                fs._batches = getattr(fs, "_batches", 0) + 1
                if rconf.results:
                    header["res"] = True
                    cost = np.asarray(cost, np.int64)
                    plen = np.asarray(plen, np.int64)
                    fin_u8 = np.asarray(fin).astype(np.uint8)
                    if rconf.answer_fp:
                        # integrity wire extension: fingerprint the
                        # segments at birth, ride the reply header; the
                        # corrupt-answer fault fires AFTER so the
                        # head's check is what must catch it
                        header["fp"] = answer_fingerprint(
                            cost, plen, fin_u8)
                        if faults.inject("corrupt-answer",
                                         fs.wid) is not None:
                            cost = cost.copy()
                            if len(cost):
                                cost[0] ^= 1
                    arrays += [cost, plen, fin_u8]
                if paths is not None:
                    header["paths"] = True
                    arrays += [np.asarray(paths[0], np.int64),
                               np.asarray(paths[1], np.int64)]
            except Exception as e:  # noqa: BLE001 — never leave the
                # client waiting on a reply that cannot come; FAIL it
                log.exception("rpc batch failed: %s", e)
                M_BATCH_FAIL.inc()
                fs._batches = getattr(fs, "_batches", 0) + 1
                fs._batch_failures = getattr(fs, "_batch_failures",
                                             0) + 1
                fs._last_error = f"{type(e).__name__}: {e}"
                stats = _StatsRow.failed()
                header = {"kind": "rep", "id": fid}
                arrays = []
            delay = faults.inject("delay", wid=fs.wid)
            if delay is not None:
                log.warning("fault: delaying rpc reply %.2fs",
                            delay.delay)
                _time.sleep(delay.delay)
            if faults.inject("drop-reply", wid=fs.wid) is not None:
                log.error("fault: dropping rpc reply id=%r", fid)
                M_RPC_DROPPED.inc()
                return True
            header["stats"] = stats.encode_wire()
            self._reply(writer, header, arrays)
            M_RPC_BATCHES.inc()
            with self._lock:
                self._served += 1
            return True
        finally:
            with self._lock:
                self._inflight -= 1

    def _answer(self, rconf, queries, diff, header):
        """The engine answer under the cross-transport mutex, with
        worker-side span capture shipped back IN the reply header
        (``trace`` events) instead of a ``.trace`` sidecar file."""
        fs = self.fs
        if rconf.trace_id:
            with obs_trace.capture(rconf.trace_id) as cap:
                with fs.answer_lock:
                    out = fs.answer_queries(queries, rconf, diff)
            header["trace"] = cap.events
            return out
        with fs.answer_lock:
            return fs.answer_queries(queries, rconf, diff)

    def _reply(self, writer, header, arrays=()) -> None:
        from ..transport import frames

        try:
            writer.send(header, arrays)
        except frames.TransportError as e:
            # client vanished before the reply: drop, never crash the
            # conn loop (its next recv sees the same dead socket)
            log.warning("rpc reply dropped: %s", e)
            M_RPC_DROPPED.inc()

    def broadcast(self, tick: dict) -> None:
        """Push one telemetry tick on every live connection — fire and
        forget, no ``id``, no reply. A dead socket just drops its copy
        (its conn loop is already on the way out); the FrameWriter lock
        keeps the push from interleaving with an in-flight reply."""
        from ..transport import frames

        with self._lock:
            writers = list(self._writers.values())
        for w in writers:
            try:
                w.send({"kind": "telemetry", "tick": tick})
            except frames.TransportError as e:
                log.debug("telemetry broadcast dropped: %s", e)

    # ------------------------------------------------------------- status
    def statusz(self) -> dict:
        with self._lock:
            return {
                "endpoint": getattr(self, "endpoint", ""),
                "connections": len(self._conns),
                "inflight": int(self._inflight),
                "credit": int(self.credit),
                "served": int(self._served),
            }


def stop_server(command_fifo: str, deadline_s: float = 2.0) -> bool:
    """Push the stop token; never wedge the caller.

    A blocking ``open(fifo, "w")`` hangs forever when the server is
    already dead (a hard crash leaves the FIFO behind with no reader), so
    open non-blocking and give up — logged, not raised — after
    ``deadline_s``. Returns True iff the token was delivered. A live
    server always has a reader (its own ``O_RDWR`` open), so the fast
    path succeeds on the first try.
    """
    import errno
    import time as _time

    deadline = _time.monotonic() + deadline_s
    fd = -1
    while fd < 0:
        try:
            fd = os.open(command_fifo, os.O_WRONLY | os.O_NONBLOCK)
        except OSError as e:
            if e.errno == errno.ENOENT:
                log.info("no FIFO at %s; server already gone",
                         command_fifo)
                return False
            if e.errno != errno.ENXIO:
                log.error("cannot open %s to stop server: %s",
                          command_fifo, e)
                return False
            if _time.monotonic() > deadline:
                log.warning("no server reading %s within %.1fs; "
                            "skipping stop", command_fifo, deadline_s)
                return False
            _time.sleep(0.05)
    try:
        os.write(fd, (STOP_TOKEN + "\n").encode())
        return True
    except OSError as e:
        log.warning("stop token to %s failed: %s", command_fifo, e)
        return False
    finally:
        os.close(fd)


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-c", default="./example-cluster-conf.json",
                   help="cluster config JSON")
    p.add_argument("-w", "--workerid", type=int, required=True)
    p.add_argument("--fifo", default=None,
                   help="command FIFO path override")
    p.add_argument("--alg", default="table-search",
                   choices=["table-search", "astar"],
                   help="serving algorithm (reference hard-codes "
                        "table-search, make_fifos.py:20; astar serves the "
                        "hscale/fscale family)")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.add_argument("--metrics-dump", default="",
                   help="write a JSON metrics snapshot (obs.metrics) to "
                        "this path on clean shutdown")
    p.add_argument("--obs-port", type=int, default=None,
                   help="serve live /metrics /healthz /statusz on this "
                        "port (0 = ephemeral; default off; "
                        "DOS_OBS_PORT)")
    p.add_argument("--traffic-dir", default=None,
                   help="diff segment stream directory: gate requests "
                        "whose diff epoch is newer than the stream "
                        "shows (STALE_DIFF wire sentinel)")
    p.add_argument("--rpc-socket", default=None,
                   help="unix socket for the streaming RPC serve loop "
                        "(default under DOS_TRANSPORT=rpc/auto: "
                        "DOS_RPC_SOCKET_DIR/dos-rpc-worker<wid>.sock)")
    p.add_argument("--rpc-port", type=int, default=None,
                   help="TCP port for the RPC serve loop (cross-host; "
                        "DOS_RPC_PORT+wid when the env base is set)")
    args = p.parse_args(argv)
    set_verbosity(args.verbose)
    from ..utils.compile_cache import use_compile_cache

    use_compile_cache()
    set_worker_id(args.workerid)

    conf = ClusterConfig.load(args.c)
    server = FifoServer(conf, args.workerid, command_fifo=args.fifo,
                        alg=args.alg, traffic_dir=args.traffic_dir)
    # the streaming data plane serves BESIDE the FIFO loop (same
    # engine, same gates): on under DOS_TRANSPORT=rpc/auto or when an
    # explicit endpoint flag names one; off (byte-identical legacy)
    # under the default DOS_TRANSPORT=fifo
    from ..transport import rpc as rpc_transport
    rpc_loop = None
    want_rpc = (args.rpc_socket is not None or args.rpc_port is not None
                or rpc_transport.resolve_transport() != "fifo")
    if want_rpc:
        port = args.rpc_port
        if port is None:
            base = env_cast("DOS_RPC_PORT", 0, int)
            port = base + args.workerid if base > 0 else None
        rpc_loop = RpcServeLoop(server, socket_path=args.rpc_socket,
                                tcp_port=port).start()
        server.rpc_loop = rpc_loop
    from ..obs.http import start_obs_server
    obs_srv = start_obs_server(
        args.obs_port, health_fn=server.health,
        status_providers={"worker": server.statusz})
    # fleet telemetry: push this worker's counters/gauges/windows to the
    # head on the DOS_TELEMETRY_INTERVAL_S cadence — over the RPC lane
    # when it serves (a `telemetry` frame on every live connection) and
    # always via the FIFO sidecar file the head polls
    from ..obs import telemetry as obs_telemetry
    publisher = None
    if obs_telemetry.interval_s() > 0:
        sinks = [obs_telemetry.sidecar_sink(
            server.command_fifo + obs_telemetry.SIDECAR_SUFFIX)]
        if rpc_loop is not None:
            sinks.append(rpc_loop.broadcast)
        publisher = obs_telemetry.TelemetryPublisher(
            source=f"w{args.workerid}", sinks=sinks).start()
        server.telemetry = publisher
    try:
        server.serve_forever()
    finally:
        if publisher is not None:
            publisher.stop()
        if rpc_loop is not None:
            rpc_loop.stop()
        if obs_srv is not None:
            obs_srv.close()
        if args.metrics_dump:
            obs_metrics.REGISTRY.dump_json(args.metrics_dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
