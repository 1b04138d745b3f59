"""Observability: metrics registry + span tracing for the query path.

The serving system fans query batches from a head node to shard-owning
workers (``cli.process_query`` → FIFO wire → ``worker.server`` →
``worker.engine``); this package is the standing instrumentation layer
every perf/robustness change reports through:

* :mod:`.metrics` — thread-safe counters / gauges / histograms with JSON
  snapshot and Prometheus text exposition (``--metrics-dump PATH``, and
  ``bench.py`` embeds a snapshot in ``BENCH_DETAIL.json``); per-worker
  name suffixes (``serve_queue_depth_w3``) fold into
  ``{worker="3"}`` labels on the text exposition;
* :mod:`.trace` — nested spans with two sinks: a
  ``jax.profiler.TraceAnnotation`` on the profile's host plane whenever
  JAX is imported and a profile records (same clock as the device's
  programs), and Chrome trace-event JSON (``--trace PATH``, open in
  Perfetto), with a per-batch ``trace_id`` propagated head→worker as a
  ``RuntimeConfig`` wire extension so both sides of one batch join on a
  single timeline;
* :mod:`.quantiles` — live sliding-window p50/p95/p99 over the last N
  seconds (``DOS_OBS_WINDOW_S``) for the latency histograms that matter
  online (``serve_request_seconds``, ``serve_dispatch_seconds``,
  ``worker_search_seconds``), each window keeping a worst-case
  **exemplar** that links a bad p99 to its timeline: the batch
  (``w<shard>.b<n>``) on the serving path, the wire ``trace_id`` on
  the campaign path;
* :mod:`.http` — the stdlib scrape server every resident process opts
  into with ``--obs-port`` / ``DOS_OBS_PORT``: ``/metrics`` (Prometheus
  text incl. live quantiles + per-program XLA costs), ``/healthz``
  (200/503 with ``HealthStatus`` semantics), ``/statusz`` (JSON:
  breakers, queue depths, replica/failover map, hedge rates, ledger
  progress);
* :mod:`.fleet` — head-side aggregation behind the ``dos-obs`` CLI:
  merge per-worker ``obs_metrics.json`` into ``fleet_metrics.json``,
  merge head + worker ``.trace`` sidecars into one campaign-wide
  Perfetto timeline, poll ``/statusz`` for a live fleet table, and
  gate ``BENCH_r*.json`` rounds against each other (``bench-diff``);
* :mod:`.device` — per-compiled-program XLA ``cost_analysis`` /
  ``memory_analysis`` capture (FLOPs, bytes accessed, HBM footprint)
  keyed by the engine's program cache, feeding the ``/metrics``
  ``device_program_*`` gauges and the roofline fields in
  ``BENCH_DETAIL.json``.

Mapping to the reference paper's per-batch stats fields (the wire CSV,
``transport.wire.ENGINE_STAT_FIELDS``) — the histograms decompose what
the reference reports only as three wall-clock totals:

=============  =====================================================
stats field    obs metrics covering the same interval
=============  =====================================================
``t_receive``  ``worker_receive_seconds`` — batch prep INCLUDING the
               weights load; ``worker_weights_load_seconds`` is the
               contained sub-phase (diff read + device upload), NOT an
               additional interval. The query-file read happens in the
               server, outside the engine's timers, and appears as the
               ``worker.receive`` span only.
``t_astar``    ``worker_search_seconds`` (the search call itself, walk
               launch until the device finished; first-call XLA
               compile time is split out into
               ``worker_jit_compile_seconds`` so steady-state latency
               is not polluted by one-time compilation). The answers'
               fetch to the host comes AFTER it, in
               ``worker_fetch_seconds`` (table-search: device slices,
               transfers, unsort, fan-out), outside every stats field
``t_search``   receive + search — the worker's whole batch but the
               fetch; the head-side view of the same batch is
               ``head_prepare_seconds`` + ``head_send_seconds``
               (FIFO round-trip, includes the worker's t_search)
=============  =====================================================

Campaign-path volume/phase series (head and worker sides of the same
batches): ``head_batches_total`` / ``head_batches_failed_total`` and
``head_partition_seconds`` / ``head_prepare_seconds`` /
``head_send_seconds`` / ``head_search_seconds`` on the head;
``worker_batches_total`` / ``worker_queries_total`` and
``server_replies_sent_total`` on the worker (sent replies are the
complement of the drop counters below).

Server failure paths (no stats-field analog — the reference dropped
these on the floor): ``server_frames_received_total``,
``server_frames_malformed_total``, ``server_frames_half_total``,
``server_replies_dropped_total``, ``server_ping_replies_dropped_total``
(control-frame drops split out so they never pollute the data-plane
drop alert), ``server_batches_failed_total``, and
``server_reply_open_wait_seconds`` (how long replies waited for the
head's answer-FIFO reader).

Fault-tolerance layer (PR 2 — every recovery path proves it fired
through one of these):

* head retries / circuit breaking — ``head_retries_total``,
  ``head_circuit_open_total``, ``head_circuit_rejected_total``,
  ``head_circuit_closed_total``, ``head_circuit_half_open_total``,
  ``head_circuits_open`` (gauge), ``head_stale_fifos_cleaned_total``;
* liveness — ``head_probes_total`` / ``head_probe_failures_total``
  (``transport.fifo.probe``) and ``server_pings_answered_total``
  (the ``__DOS_PING__`` control frame);
* supervision — ``supervisor_respawns_total``,
  ``supervisor_pings_total``, ``supervisor_ping_failures_total``,
  ``supervisor_workers_alive`` (gauge);
* fault harness — ``faults_injected_total`` (``DOS_FAULTS`` rules that
  fired; in a chaos run the recovery counters above should move in
  lock-step with it).

Online serving layer (``serving/`` — the open-workload frontend; every
admission decision, batch, and cache outcome is visible):

* requests — ``serve_requests_total`` / ``serve_requests_ok_total``,
  end-to-end ``serve_request_seconds`` (submit → completion, cache hits
  included);
* admission control — ``serve_shed_busy_total`` (queue full),
  ``serve_shed_unavailable_total`` (open breaker / shutdown),
  ``serve_timeouts_total`` (deadline expired while queued),
  ``serve_errors_total``; ``serve_queue_depth`` gauge;
* micro-batching — ``serve_batches_total``, ``serve_batch_fill`` and
  ``serve_time_to_flush_seconds`` histograms (is coalescing working?),
  ``serve_flush_full_total`` vs ``serve_flush_wait_total`` (which
  trigger fired), ``serve_dispatch_seconds``,
  ``serve_batches_in_flight`` gauge;
* result cache — ``serve_cache_{hits,misses,evictions}_total``,
  ``serve_cache_{entries,bytes}`` gauges;
* worker-side dedup (the batch-level twin of the cache) —
  ``worker_duplicate_queries_total``;
* stage waits and spans (every batch carries a shard-local number,
  ``batch=`` on each span below) — ``serve_queue_wait_seconds`` (each
  request, enqueued until the shard's runner pops it into a batch;
  span ``serve.wait``, the runner waiting in ``get_batch``),
  ``serve_handoff_wait_seconds`` (each batch, flushed until its
  dispatch starts: the runner's own bookkeeping, microseconds), then
  on the runner ``serve.dispatch``
  around ``worker.prep`` (``worker_receive_seconds``, with
  ``worker.weights`` nested), ``worker.walk``
  (``worker_search_seconds``) and ``worker.fetch``
  (``worker_fetch_seconds``), and ``serve.finish`` (cache puts and
  futures set, inside ``serve_dispatch_seconds``);
  ``worker_device_gap_seconds`` (table-search batches after an
  engine's first: the previous batch's answers on the host until this
  walk's launch — host time with nothing of the engine's queued on
  its device).

Artifact durability layer (the index data plane — atomic writes,
checksummed manifests, crash-resume, self-healing loads; see the
README's "Artifact durability & resume"):

* load/verify — ``cpd_blocks_verified_total`` (blocks that passed the
  digest/shape check), ``cpd_blocks_corrupt_total`` (missing, torn, or
  digest-mismatched blocks found at load or ``make_cpds --verify``),
  ``cpd_blocks_rebuilt_total`` (quarantined blocks rebuilt in place
  from the graph); ``cpd.verify`` / ``cpd.rebuild`` spans carry the
  per-block timings;
* crash-resume — ``build_blocks_resumed_total`` (blocks a restarted
  build skipped because the per-worker ledger records them complete
  with a matching on-disk digest);
* build pipeline (``models.cpd.build_worker_shard`` — async
  host→device staging) — ``build_rows_staged_total`` (rows whose
  frontier/target inputs the host stager prepared),
  ``build_stage_overlap_seconds`` (host staging time per block:
  padded-target device upload + pre-opened block writer, overlapped
  with device compute when the pipeline is on),
  ``build_pipeline_stall_seconds`` (time the device-dispatch loop
  waited on the stager — the number the pipeline drives toward zero);
* delta rebuilds (``models.cpd.delta_build_index`` — epoch-keyed
  incremental CPD refresh) — ``build_delta_rows_recomputed_total``
  (rows the tense-edge pass marked dirty and the delta recomputed),
  ``build_delta_skipped_blocks_total`` (blocks reused as byte copies
  from the old index, digests journaled, zero device work);
* sweep — ``artifacts_swept_total`` (stale ``*.tmp`` debris and
  leftover ``*.quarantined`` blocks removed at build/campaign start,
  the artifact-plane analog of ``head_stale_fifos_cleaned_total``).

Replication layer (R-way shard replication — failover routing, hedged
dispatch, replica anti-entropy; README "Replication & failover"):

* failover — ``failover_total`` (batches re-routed off a dead/failed
  primary to a live replica; booked by the campaign head's
  ``send_failover`` AND the serving frontend's dispatch loop),
  ``server_replica_batches_total`` (batches a worker answered from a
  hosted replica shard — the worker-side view of the same traffic);
* hedging — ``hedges_issued_total`` / ``hedges_won_total`` (duplicates
  sent after the adaptive per-shard latency-quantile delay, and how
  often the replica beat the primary),
  ``hedges_budget_denied_total`` (hedges declined by the
  ``DOS_HEDGE_BUDGET`` rate cap — the overload-amplification guard),
  per-shard ``serve_queue_depth_w<wid>`` gauges (failover load shifts
  made visible per queue);
* anti-entropy — ``replica_digest_mismatches_total`` (replica blocks
  whose crc32 diverged from their primary's; quarantined + healed),
  ``replica_blocks_copied_total`` (replica blocks materialized by
  copying a digest-valid primary instead of recomputing).

Elastic fleet membership (``parallel.membership`` — epoch-versioned
shard→worker assignment, drain-free join/leave; README "Elastic
fleet"):

* epoch / reconfiguration — ``reshard_epoch`` (gauge: the committed
  partition-table epoch; 0 = the static pre-elastic fleet),
  ``reshard_migrations_total`` (windows begun),
  ``reshard_shards_moved_total`` (ownership transfers committed),
  ``reshard_aborted_total`` (windows closed without the bump),
  ``reshard_leave_refused_total`` (leave plans refused because a shard
  had no live replica-chain adopter — R=1 sole owner; refusing beats
  stranding it mid-window),
  ``reshard_catchup_seconds`` (per-shard adopter verify+heal);
* catch-up data plane — ``reshard_blocks_adopted_total`` (blocks
  digest-verified/healed by an adopting worker; the heal path itself
  books the ``cpd_blocks_*`` series as usual);
* version gate — ``server_stale_epoch_total`` (batches a worker
  refused with the ``STALE_EPOCH`` wire sentinel: routed under a
  NEWER table than the worker could see even after a membership
  refresh).

Live traffic plane (``traffic/`` — streaming congestion diffs, scoped
cache invalidation, and the typed query families; README "Live
traffic"):

* epoch swaps — ``traffic_epoch`` (gauge: the active diff epoch, 0 =
  the static base diff), ``traffic_segments_applied_total`` (stream
  segments fused into swaps), ``traffic_edges_updated_total`` (edges
  whose weight actually changed), ``traffic_swap_seconds`` (segment
  merge + fused-diff materialization per swap);
* scoped invalidation — ``serve_cache_invalidated_scoped_total`` /
  ``serve_cache_invalidated_full_total`` (entries dropped by reason:
  a SCOPED pass drops only entries whose cached path touches an
  updated edge and re-keys the provable survivors; FULL counts manual
  diff changes and swaps past the ``DOS_TRAFFIC_SCOPED_MAX`` bound),
  ``serve_cache_rekeyed_total`` (the survivors a SCOPED pass re-keyed
  to the new epoch — kept / (kept + scoped-dropped) is the scoped
  hit rate the bench headlines);
* query families — ``serve_matrix_requests_total`` (one-to-many ETA
  rows), ``serve_alt_requests_total`` (k-alternative routes),
  ``serve_reverse_requests_total`` (reverse source-owner routing),
  ``serve_shed_family_total`` (typed family requests answered BUSY by
  the control plane's brownout ladder — level >= 2 sheds mat/alt
  while plain pair queries keep flowing);
* version gate — ``server_stale_diff_total`` (batches a worker refused
  with the ``STALE_DIFF`` wire sentinel: fused at a NEWER diff epoch
  than the worker's segment stream shows even after a refresh — the
  traffic twin of ``server_stale_epoch_total``).

Live observability plane (this PR's standing layer — the scrape-time
series every resident process exposes):

* scrape endpoints — ``obs_scrapes_total`` (requests answered by
  ``/metrics`` / ``/healthz`` / ``/statusz``);
* live quantiles (``obs.quantiles``, window gauges on ``/metrics``
  only, not in JSON snapshots) —
  ``serve_request_seconds_window{quantile=...}`` with
  ``serve_request_seconds_window_worst{trace_id=...}`` exemplar,
  likewise for ``serve_dispatch_seconds`` and
  ``worker_search_seconds``;
* per-worker labels — ``serve_queue_depth{worker="N"}`` is the text-
  exposition form of the flat ``serve_queue_depth_w<N>`` gauges (JSON
  snapshots keep the flat names);
* XLA program costs (``obs.device``) — ``device_programs_analyzed``
  (gauge) plus per-program ``device_program_flops`` /
  ``device_program_bytes_accessed`` / ``device_program_hbm_bytes``
  labeled gauges, captured once per engine program-cache key and
  embedded in ``BENCH_DETAIL.json`` as the roofline denominators;
* walk-kernel selection (``ops.pallas_walk`` via ``worker.engine``) —
  ``walk_{pallas,xla}_batches_total``: table-search batches by the
  kernel that answered them (``DOS_WALK_KERNEL`` resolution; a
  pallas request that cannot run is refused and books neither), next
  to the ``table-search[pallas]/...`` program cost capture.

Worker mesh (multi-device sharded execution — one worker driving a
lane mesh, ``DOS_MESH_DEVICES``; README "Worker mesh"):

* ``mesh_devices`` (gauge) — devices in this worker's local lane mesh
  (1 = the legacy single-device engine);
* ``mesh_walk_batches_total`` — table-search batches split across the
  worker's mesh lanes (per-device bucket subsets under shard_map,
  bit-identical unsort);
* ``mesh_collective_seconds`` — on-mesh collective join per mat-family
  row (``CPDOracle.query_mat``: walk + scatter + psum, replacing the
  head-side fan-out/join).

Streaming RPC data plane (``transport.frames``/``transport.rpc`` +
the worker's socket accept loop — persistent multiplexed connections
replacing per-batch files and FIFO round-trips, ``DOS_TRANSPORT``;
README "Streaming data plane"):

* frame codec — ``rpc_frames_sent_total`` / ``rpc_frames_received_total``
  (every frame on every socket, both directions),
  ``rpc_frames_torn_total`` (frames that died mid-read: peer gone,
  reset, bad magic — each surfaced as a retryable TransportError);
* client connections — ``rpc_connects_total`` /
  ``rpc_reconnects_total`` (persistent connections established /
  re-established after a failure), ``rpc_transport_errors_total``
  (calls failed by transport faults, the breaker/failover feed),
  ``rpc_heartbeats_total`` (pings riding the HealthStatus vocabulary
  over live connections, ``DOS_RPC_HEARTBEAT_S``);
* backpressure — ``rpc_busy_frames_total`` (explicit BUSY credit-
  window refusals, client and server sides both book here — the
  timeout-discovery replacement);
* dispatch — ``rpc_dispatch_seconds`` (one serving batch over the
  socket transport, send to decoded reply);
* worker accept loop — ``rpc_server_connections`` (gauge: live client
  connections), ``rpc_server_batches_total`` (batches answered over
  sockets — the RPC twin of ``server_replies_sent_total``),
  ``rpc_server_replies_dropped_total`` (drop-reply fault or the
  client vanished), ``rpc_server_frames_malformed_total``
  (undecodable request configs answered FAIL — the socket twin of
  ``server_frames_malformed_total``);
* hedged FIFO dispatch (the compat backend's satellite fix) —
  ``serve_hedge_qfile_reused_total`` (hedge duplicates that reused
  the primary attempt's already-written query file instead of paying
  a second filesystem round-trip per candidate).

Gateway tier (``gateway/`` — N stateless frontends behind a binary
client protocol, plus the shard-owner L2 result cache,
``DOS_GATEWAY_*``; README "Gateway tier"):

* client ingress — ``gateway_requests_total`` (frames received on
  client connections: queries, hellos, pings),
  ``gateway_queries_total`` (individual queries inside batched query
  frames, all families), ``gateway_clients`` (gauge: live client
  connections across this process's frontends);
* backpressure — ``gateway_busy_total`` (query frames refused with an
  explicit BUSY because the connection's credit window was full — the
  gateway twin of ``rpc_busy_frames_total``);
* the gateway's own time — ``gateway_frame_seconds`` (each admitted
  query frame, read off the socket until its reply was written; span
  ``gateway.frame`` on the reader for parse and submit) and
  ``gateway_reply_seconds`` (each answered pair frame, its last answer
  until its reply was written: writer wake, in-order wait, encode,
  send; span ``gateway.reply`` on the writer, carrying the frame's
  ``batches``);
* protocol hygiene — ``gateway_frames_malformed_total`` (client
  frames that failed to decode and were answered with a typed ERROR
  frame instead of a torn connection);
* shard-owner L2 cache — ``worker_l2_hits_total`` (queries answered
  from the worker's ``(s, t, diff-epoch)`` cache before the kernel)
  and ``worker_l2_misses_total`` (L2 lookups that fell through to the
  kernel); ``gateway_l2_admit_denied_total`` (inserts withheld by the
  second-hit admission doorkeeper,
  ``DOS_GATEWAY_L2_ADMIT=second-hit``); entry counts and per-replica
  hit rates ride ``/statusz``, not the registry;
* high availability (leased endpoint registry + client failover,
  README "Gateway HA") — ``gateway_lease_renewals_total`` (endpoint
  lease heartbeats written to ``gateway.json``),
  ``gateway_live_frontends`` (gauge: frontends with an unexpired
  lease at the last registry read), ``gateway_client_failovers_total``
  (client connection moves to another live frontend, unanswered
  frames resubmitted under their original ids),
  ``gateway_resubmits_deduped_total`` (resubmitted frames a frontend
  had already answered, replayed from the ``(cid, id)`` memo — the
  exactly-once accounting guarantee), and
  ``gateway_failover_frames_total`` (resubmitted frames re-executed
  on a frontend that had NOT answered them — the at-least-once
  execution half; answers stay bit-identical).

Compressed residency (``models.resident`` — RLE/pack4 CPD shards kept
compressed in device memory and decompressed only at the point of use,
``DOS_CPD_RESIDENT``; README "Compressed residency"):

* ``cpd_resident_bytes`` (gauge) — device bytes of the most recently
  materialized resident first-move table after codec selection (the
  raw bytes when the codec degraded);
* ``cpd_resident_degraded_total`` — resident tables whose requested
  codec was not viable (escape slots for pack4, incompressible runs
  for rle) and were served raw instead — the fit-degrade is a
  counter, never a fault;
* ``cpd_decompress_seconds`` — per-batch decompress-at-use (pack4
  nibble unpack / rle run-start search) before the walk kernel runs;
* ``walk_compressed_batches_total`` — table-search batches answered
  from a compressed-resident shard (the Pallas kernel's
  decompress-on-tile path or the XLA run-start decode feeding either
  kernel).

Fleet telemetry bus (``obs.telemetry`` + ``obs.timeseries`` — workers
push delta-encoded metric snapshots to the head over the RPC wire or
the FIFO lane's ``.telemetry`` sidecar, ``DOS_TELEMETRY_INTERVAL_S``;
README "Fleet telemetry & SLOs"):

* publisher — ``telemetry_ticks_published_total`` (snapshots emitted
  on the cadence), ``telemetry_publish_errors_total`` (sinks that
  raised; per-sink, the tick still reaches the others),
  ``telemetry_publish_seconds`` (one tick build+fan-out — the bench's
  publish-overhead numerator), ``rpc_heartbeat_seconds`` window
  (heartbeat round-trips per connection, plus the per-worker
  ``rpc_heartbeat_seconds_w<wid>`` twins);
* head ingest — ``telemetry_ticks_ingested_total`` /
  ``telemetry_ticks_dropped_total`` (undecodable or wrong-shape
  ticks), ``telemetry_counter_resets_total`` (source restarts
  detected by incarnation change or counter regression — deltas clamp
  to absolute-from-zero, never negative);
* timeseries store (byte-budgeted ring, ``DOS_TELEMETRY_BYTES``) —
  ``telemetry_points_total`` (points appended),
  ``telemetry_series_evicted_total`` (rings dropped by the budget,
  oldest-written first), ``telemetry_series`` / ``telemetry_store_bytes``
  (gauges: live ring count and retained bytes).

SLO burn-rate engine (``obs.slo`` — declarative objectives evaluated
as multi-window burn rates with hysteresis, ``DOS_SLO_SPECS``; the
``/slo`` endpoint and ``dos-obs slo``):

* ``slo_evaluations_total`` / ``slo_alerts_total`` (evaluation passes,
  and alerts that TRIPPED — clears don't count);
* per-objective gauges ``slo_fast_burn_<name>`` / ``slo_slow_burn_<name>``
  (burn = bad-fraction / error-budget over the fast/slow windows) and
  ``slo_alerting_<name>`` (1 while tripped; hysteresis clears at half
  the trip threshold).

Black-box flight recorder (``obs.recorder`` — bounded on-disk ring of
telemetry ticks + structured events, ``DOS_RECORDER_DIR``; ``dos-obs
record`` / ``dos-obs replay``):

* ``recorder_events_total`` (structured events emitted fleet-wide:
  epoch swaps, breaker transitions, respawns, membership commits,
  BUSY storms, fault injections, SLO alerts/clears),
  ``recorder_records_total`` (records written to the tape),
  ``recorder_segments_total`` (segment rotations),
  ``recorder_torn_lines_total`` (torn tail lines skipped at replay),
  ``recorder_ring_bytes`` (gauge: on-disk ring footprint).

Closed-loop control (``control/`` — the policy daemon that turns the
sensors above into automatic recovery actions, ``DOS_CONTROL``;
README "Closed-loop control"):

* loop — ``control_ticks_total`` (sense->decide->act passes),
  ``control_decisions_total`` (decisions reached: executed, dry-run,
  or budget-denied), ``control_actions_total`` (actions executed),
  ``control_budget_denied_total`` (decisions past the global action
  budget), ``control_errors_total`` (actuator executions that raised);
* quarantine — ``control_quarantines_total`` (sick workers removed
  from routing: breaker pin + respawn kick),
  ``control_readmissions_total`` (re-admitted after N clean probes);
* brownout — ``control_brownout_shifts_total`` (ladder level changes),
  ``control_brownout_level`` (gauge: current level, 0 = full service);
* repair / scale — ``control_repairs_total`` (plan_join / plan_leave /
  hot-shard replication executed), ``control_scale_advised_total``
  (scale-up advisories booked where the daemon owns no actuator:
  no join host configured, or lane widening needing a worker restart);
* warming — ``control_warms_total`` (next diff epoch pre-fused /
  registered warmers run ahead of the pump cadence);
* gateway HA arm — ``control_gateway_kicks_total`` (dead gateway
  frontends kicked for respawn after their ``gateway.json`` endpoint
  lease expired).

Answer-integrity plane (``integrity/`` — resident-table scrubbing,
sampled dual-execution audit, and wire/cache answer fingerprints,
``DOS_SCRUB_*`` / ``DOS_AUDIT_*`` / ``DOS_ANSWER_FP``; README "Answer
integrity & auditing"):

* resident scrubber — ``scrub_blocks_checked_total`` (resident blocks
  crc32-compared against their digest-verified on-disk truth),
  ``scrub_blocks_corrupt_total`` (blocks whose resident rows diverged
  — silent in-memory corruption; the table re-binds from disk),
  ``scrub_passes_total`` / ``scrub_pass_seconds`` (pass cadence and
  wall cost — the overhead numerator the bench's integrity section
  holds under its budget);
* dual-execution audit — ``audit_batches_total`` (served batches
  re-executed on an independent lane: replica, CPU reference, or
  uncached recompute), ``audit_divergence_total`` (audits whose
  re-execution DISAGREED with the served answer — the wrong-answer
  alarm feeding the control loop's divergence-quarantine arm),
  ``audit_dropped_total`` (samples dropped at the bounded queue — the
  audit plane never backpressures serving), ``audit_lane_seconds``
  (one re-execution + compare, by whichever lane ran);
* answer fingerprints — ``answer_fp_mismatch_total`` (replies whose
  crc32 answer fingerprint failed verification at a dispatcher or
  results-sidecar decode; the batch fails over instead of serving
  corrupted answers), ``cache_fingerprint_mismatch_total`` (cache
  hits whose stored entry no longer matches its insertion-time
  fingerprint — dropped and recomputed, never served);
* control arm — ``control_divergence_quarantines_total`` (shards
  pulled from routing on a confirmed audit divergence: breaker
  force-open + scrub-now, re-admitted only after clean probes).
"""

from . import device, fleet, metrics, quantiles, trace
from .metrics import REGISTRY, counter, gauge, histogram
from .quantiles import WINDOWS
from .trace import span

#: imported lazily (PEP 562): these modules use ``utils.atomicio``,
#: which itself registers metrics — an eager import here would close
#: an import cycle through the package __init__
_LAZY = ("recorder", "slo", "telemetry", "timeseries")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")

__all__ = ["device", "fleet", "metrics", "quantiles", "recorder",
           "slo", "telemetry", "timeseries", "trace",
           "REGISTRY", "WINDOWS", "counter", "gauge", "histogram",
           "span"]
