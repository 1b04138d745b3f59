"""Fleet-wide aggregation: merge worker snapshots, traces, statusz.

One process's registry answers for one process; a campaign or serving
deployment is a *fleet* — a head plus N workers (plus replicas), each
already materializing ``obs_metrics.json`` snapshots and ``.trace``
span sidecars over the shared NFS data plane. This module is the
head-side merge logic behind the ``dos-obs`` CLI (``cli.obs``):

* :func:`merge_snapshots` — N labeled per-process snapshots into one
  ``fleet_metrics.json``: counters and histograms sum (bucket-wise —
  every process runs the same code, so bucket edges agree; a
  mismatched histogram degrades to count+sum), gauges sum with the
  per-worker values preserved under ``workers`` so a fleet total never
  hides a skewed replica. Duplicate labels are disambiguated
  (``w0``, ``w0#2``) rather than silently overwritten — two workers
  claiming one identity is exactly the kind of thing a merge must
  surface.
* :func:`merge_traces` — head trace files (``{"traceEvents": ...}``)
  and worker span sidecars (bare event lists) into ONE Perfetto-
  loadable timeline; events keep their pids so every process is its
  own track, and batches still join across tracks on ``trace_id``.
* :func:`fetch_statusz` / :func:`render_top` — poll live ``/statusz``
  endpoints (``obs.http``) and render the fleet table ``dos-obs top``
  shows: queue depths, open breakers, hedge rate, replica map per
  endpoint.
* :func:`compare_bench` — the regression gate behind ``dos-obs
  bench-diff``: newest ``BENCH_r*.json`` vs the previous one with
  per-key tolerances; throughput-like keys must not fall, latency-like
  keys must not rise.
"""

from __future__ import annotations

import glob
import json
import os
import re
import urllib.request

from ..utils.log import get_logger

log = get_logger(__name__)


# ------------------------------------------------------------- snapshots

def _merge_histogram(agg: dict, h: dict) -> dict:
    """Sum one histogram into the aggregate (cumulative buckets are
    additive per edge). Mismatched bucket edges — which only happens
    across code versions — degrade to count+sum."""
    if not agg:
        return {"count": h.get("count", 0), "sum": h.get("sum", 0.0),
                "buckets": dict(h.get("buckets", {}))}
    agg = {"count": agg.get("count", 0) + h.get("count", 0),
           "sum": agg.get("sum", 0.0) + h.get("sum", 0.0),
           "buckets": dict(agg.get("buckets", {}))}
    mine, theirs = agg["buckets"], h.get("buckets", {})
    if set(mine) == set(theirs):
        for le in mine:
            mine[le] += theirs[le]
    else:
        log.warning("histogram bucket edges differ across workers; "
                    "keeping count+sum only")
        agg["buckets"] = {}
    return agg


def dedupe_labels(labels: list[str]) -> list[str]:
    """Disambiguate duplicate worker labels in input order:
    ``w0, w0 -> w0, w0#2``."""
    seen: dict[str, int] = {}
    out = []
    for lab in labels:
        n = seen.get(lab, 0) + 1
        seen[lab] = n
        out.append(lab if n == 1 else f"{lab}#{n}")
    return out


def merge_snapshots(inputs: list[tuple[str, dict]]) -> dict:
    """``[(label, snapshot), ...]`` -> the fleet document: per-worker
    snapshots under ``workers`` (labels deduped), summed counters /
    gauges / histograms under ``fleet``."""
    labels = dedupe_labels([lab for lab, _ in inputs])
    workers = {lab: snap for lab, (_, snap) in zip(labels, inputs)}
    fleet = {"counters": {}, "gauges": {}, "histograms": {}}
    for snap in workers.values():
        for name, v in snap.get("counters", {}).items():
            fleet["counters"][name] = fleet["counters"].get(name, 0) + v
        for name, v in snap.get("gauges", {}).items():
            fleet["gauges"][name] = fleet["gauges"].get(name, 0) + v
        for name, h in snap.get("histograms", {}).items():
            fleet["histograms"][name] = _merge_histogram(
                fleet["histograms"].get(name, {}), h)
    return {"workers": workers, "fleet": fleet,
            "n_workers": len(workers)}


def load_snapshot_files(paths: list[str],
                        labels: list[str] | None = None) -> list:
    """Read snapshot JSONs into ``merge_snapshots`` input. Default
    labels come from the parent dir + filename, which is how per-worker
    artifact dirs differ."""
    out = []
    for i, p in enumerate(paths):
        with open(p) as f:
            snap = json.load(f)
        if labels and i < len(labels):
            lab = labels[i]
        else:
            lab = os.path.join(os.path.basename(os.path.dirname(p)),
                               os.path.basename(p))
        out.append((lab, snap))
    return out


# ---------------------------------------------------------------- traces

def _events_of(path: str) -> list[dict]:
    """Events from either container format: a full Chrome trace doc
    (``{"traceEvents": [...]}``) or a bare sidecar list."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        evs = doc.get("traceEvents", [])
    else:
        evs = doc
    if not isinstance(evs, list):
        raise ValueError(f"{path}: no trace events found")
    return evs


def merge_traces(inputs: list[str], out_path: str) -> int:
    """Merge trace files/sidecars (directories glob ``*.trace``) into
    one Perfetto-loadable Chrome trace doc. Returns the event count."""
    paths = []
    for p in inputs:
        if os.path.isdir(p):
            paths.extend(sorted(glob.glob(os.path.join(p, "*.trace"))))
        else:
            paths.append(p)
    events: list[dict] = []
    for p in paths:
        evs = _events_of(p)
        events.extend(evs)
        log.info("merge-traces: %s -> %d event(s)", p, len(evs))
    events.sort(key=lambda e: e.get("ts", 0))
    from ..utils.atomicio import atomic_write_bytes
    atomic_write_bytes(out_path, json.dumps(
        {"traceEvents": events, "displayTimeUnit": "ms"},
        indent=1).encode())
    return len(events)


# --------------------------------------------------------------- statusz

def fetch_json(endpoint: str, path: str = "/statusz",
               timeout_s: float = 3.0) -> dict:
    """``host:port`` + path -> its JSON (``{"error": ...}`` when
    unreachable — a dead worker is a row in the fleet table, not a
    crash of the tool watching for dead workers)."""
    url = endpoint if "://" in endpoint else f"http://{endpoint}"
    try:
        with urllib.request.urlopen(f"{url}{path}",
                                    timeout=timeout_s) as r:
            return json.loads(r.read().decode())
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}


def fetch_statusz(endpoint: str, timeout_s: float = 3.0) -> dict:
    return fetch_json(endpoint, "/statusz", timeout_s=timeout_s)


def _summarize(status: dict) -> dict:
    """Flatten one endpoint's statusz into the fleet-table columns.

    Schema-heterogeneous by design: a rolling upgrade mixes workers
    that export the elastic-membership keys (``epoch``, ``migration``)
    with workers that predate them — a missing or oddly-typed key
    renders as a blank cell in that endpoint's row, never a crash of
    the tool watching the upgrade."""
    if "error" in status:
        return {"state": "UNREACHABLE", "detail": status["error"]}

    def _num(v, default=0):
        # bool is an int subclass but not a count; null/str render as
        # the default instead of raising out of a sum()
        return (v if isinstance(v, (int, float))
                and not isinstance(v, bool) else default)

    out: dict = {"state": "up"}
    serving = status.get("serving", {})
    if not isinstance(serving, dict):
        serving = {}
    if serving:
        shards = serving.get("shards", {})
        if isinstance(shards, dict):
            out["queued"] = sum(_num(s.get("queue_depth"))
                                for s in shards.values()
                                if isinstance(s, dict))
            out["shards"] = len(shards)
        hedge = serving.get("hedge", {})
        if isinstance(hedge, dict) and hedge:
            out["hedge_rate"] = _num(hedge.get("rate"), 0.0)
    # the serve frontend nests its breaker section under "serving";
    # a bare BreakerRegistry provider sits at the top level
    braw = serving.get("breakers") or status.get("breakers") or {}
    breakers = (braw.get("breakers", {}) if isinstance(braw, dict)
                else {})
    if isinstance(breakers, dict) and breakers:
        out["breakers_open"] = sum(
            1 for b in breakers.values()
            if isinstance(b, dict)
            and b.get("state") in ("open", "half-open"))
    worker = status.get("worker", {})
    if not isinstance(worker, dict):
        worker = {}
    if worker:
        out["batches"] = _num(worker.get("batches"))
        out["failures"] = _num(worker.get("batch_failures"))
    sup = status.get("supervisor", {})
    if isinstance(sup, dict) and sup:
        out["alive"] = _num(sup.get("alive"))
        out["respawns"] = _num(sup.get("respawns"))
    # elastic-membership columns: present only when the endpoint
    # exports them (a pre-elastic worker's row shows "-" blanks)
    for sec in (serving, worker):
        if "epoch" in sec and isinstance(sec["epoch"], (int, float)):
            out["epoch"] = int(sec["epoch"])
            break
    # live-traffic column: the active DIFF epoch — same mixed-schema
    # tolerance (a pre-traffic endpoint's row shows a blank)
    for sec in (serving, worker):
        if ("diff_epoch" in sec
                and isinstance(sec["diff_epoch"], (int, float))
                and not isinstance(sec["diff_epoch"], bool)):
            out["diff epoch"] = int(sec["diff_epoch"])
            break
    # worker-mesh column: lanes per worker (multi-device engines) —
    # same mixed-schema tolerance: an older worker omits the key (or
    # ships an odd type) and its row shows a blank, never a crash
    for sec in (serving, worker):
        mesh = sec.get("mesh")
        if (isinstance(mesh, dict)
                and isinstance(mesh.get("devices"), (int, float))
                and not isinstance(mesh.get("devices"), bool)):
            out["mesh"] = int(mesh["devices"])
            break
    # streaming-transport columns (the RPC data plane): connections,
    # in-flight frames, credit window — a worker row reads its accept
    # loop, a head row folds its per-worker client table. Pre-RPC
    # endpoints omit the section and their rows show "-" blanks, never
    # a crash (the same mixed-schema tolerance as every other column)
    for sec in (serving, worker):
        tr = sec.get("transport")
        if not isinstance(tr, dict) or not tr:
            continue
        conns = tr.get("connections")
        if isinstance(conns, dict):
            # head side (RpcDispatcher/AutoDispatcher): one entry per
            # worker connection
            out["conns"] = len(conns)
            out["inflight"] = sum(
                _num(c.get("inflight")) for c in conns.values()
                if isinstance(c, dict))
        elif isinstance(conns, (int, float)) \
                and not isinstance(conns, bool):
            # worker side (RpcServeLoop.statusz)
            out["conns"] = int(conns)
            out["inflight"] = _num(tr.get("inflight"))
        credit = tr.get("credit")
        if isinstance(credit, (int, float)) \
                and not isinstance(credit, bool):
            out["credit"] = int(credit)
        break
    # gateway-tier columns: replica identity, client connections, and
    # the two cache levels' hit rates. A gateway process ships a
    # top-level "gateway" section (a tier reports its replica count, a
    # single replica its frontend id), a worker ships "l2" under its
    # worker section; pre-gateway fleets omit both and their rows show
    # "-" blanks, never a crash
    gw = status.get("gateway")
    if isinstance(gw, dict) and gw:
        reps = gw.get("replicas")
        fe_id = gw.get("frontend")
        if isinstance(reps, (int, float)) \
                and not isinstance(reps, bool):
            out["gw"] = f"x{int(reps)}"
        elif isinstance(fe_id, (int, float)) \
                and not isinstance(fe_id, bool):
            out["gw"] = f"f{int(fe_id)}"
        clients = gw.get("clients")
        if isinstance(clients, (int, float)) \
                and not isinstance(clients, bool):
            out["clients"] = int(clients)
        l1 = gw.get("l1_hit_rate")
        if isinstance(l1, (int, float)) and not isinstance(l1, bool):
            out["l1 hit"] = round(float(l1), 2)
        # HA columns (PR 19): fleet-wide live peer count from the
        # endpoint registry, worst lease age across local replicas,
        # and frames re-executed here after a client failover. Pre-HA
        # gateways omit all three — blanks, never a crash
        peers = gw.get("peers")
        if isinstance(peers, (int, float)) \
                and not isinstance(peers, bool):
            out["peers"] = int(peers)
        lease = gw.get("lease_age_s")
        if isinstance(lease, (int, float)) \
                and not isinstance(lease, bool):
            out["lease s"] = round(float(lease), 1)
        fo = gw.get("failovers")
        if isinstance(fo, (int, float)) and not isinstance(fo, bool):
            out["failover"] = int(fo)
    l2 = worker.get("l2")
    if isinstance(l2, dict):
        rate = l2.get("hit_rate")
        if isinstance(rate, (int, float)) \
                and not isinstance(rate, bool):
            out["l2 hit"] = round(float(rate), 2)
    # SLO / telemetry columns (the head's fleet-health plane): worst
    # fast-burn across objectives (the page-now signal) and worst
    # telemetry source lag (a stalled publisher or dead wire shows up
    # as lag before anything else does). Pre-telemetry endpoints omit
    # both sections and their rows show "-" blanks, never a crash
    slo_sec = status.get("slo")
    if isinstance(slo_sec, dict):
        burn_sec = slo_sec.get("burn")
        burns = [_num(b.get("fast"), None)
                 for b in (burn_sec.values()
                           if isinstance(burn_sec, dict) else ())
                 if isinstance(b, dict)]
        burns = [b for b in burns if b is not None]
        if burns:
            out["slo burn"] = round(max(burns), 2)
        alerting = slo_sec.get("alerting")
        if isinstance(alerting, list) and alerting:
            out["state"] = "SLO:" + ",".join(str(a) for a in alerting)
    tele = status.get("telemetry")
    if isinstance(tele, dict):
        src_sec = tele.get("sources")
        lags = [_num(s.get("lag_s"), None)
                for s in (src_sec.values()
                          if isinstance(src_sec, dict) else ())
                if isinstance(s, dict)]
        lags = [v for v in lags if v is not None]
        if lags:
            out["tel lag"] = round(max(lags), 1)
    # closed-loop control columns: policy state (brownout level, dry-run
    # tag), last action, quarantined workers. Only a daemon-enabled
    # endpoint ships the section; every other row shows "-" blanks —
    # the same mixed-schema tolerance as the slo/telemetry columns
    ctl = status.get("control")
    if isinstance(ctl, dict) and ctl:
        lvl = ctl.get("brownout_level")
        if isinstance(lvl, (int, float)) and not isinstance(lvl, bool):
            tag = "dry:" if ctl.get("dry_run") is True else ""
            out["policy"] = f"{tag}L{int(lvl)}"
        last = ctl.get("last_action")
        if isinstance(last, str) and last:
            out["last action"] = last.split(" ", 1)[0]
        quarantined = ctl.get("quarantined")
        if isinstance(quarantined, list) and quarantined:
            out["quarantined"] = ",".join(
                str(w) for w in quarantined)
    mig = serving.get("migration") or worker.get("migration")
    if isinstance(mig, dict):
        moves = mig.get("moves") if isinstance(mig.get("moves"), list) \
            else []
        done = mig.get("done") if isinstance(mig.get("done"), list) \
            else []
        out["migration"] = (f"{mig.get('kind', '?')}->e"
                            f"{mig.get('epoch', '?')} "
                            f"{len(done)}/{len(moves)}")
    return out


def render_top(statuses: dict[str, dict]) -> str:
    """The ``dos-obs top`` fleet table: one row per endpoint, columns
    unioned across roles (a frontend shows queues/hedges, a worker
    batches/failures, a supervisor alive/respawns)."""
    rows = {ep: _summarize(st) for ep, st in statuses.items()}
    cols = ["endpoint"]
    for r in rows.values():
        for k in r:
            if k not in cols:
                cols.append(k)
    table = [cols]
    for ep, r in rows.items():
        table.append([ep] + [str(r.get(c, "-")) for c in cols[1:]])
    widths = [max(len(row[i]) for row in table)
              for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip()
             for row in table]
    lines.insert(1, "-" * len(lines[0]))
    return "\n".join(lines)


# ------------------------------------------------------------ bench gate

#: default fractional tolerance — recorded runs have swung ±20%, so
#: the gate trips only on clear breaks
DEFAULT_TOLERANCE = 0.3

#: key patterns whose value IMPROVES downward (everything else is
#: treated as higher-is-better throughput/ratio)
_LOWER_BETTER = re.compile(
    r"(_ms|_seconds|_s)$|(^|_)p\d+_ms$|break[-_]?even")

#: explicit per-key directions for headline keys whose names defeat the
#: suffix heuristic — the walk-kernel roofline family (PR 10): q/s and
#: utilization/efficiency fractions improve UP, stall/kernel time
#: improves DOWN (listed even where the suffix would catch it, so the
#: family's contract is in one place)
_KEY_DIRECTIONS = {
    "walk_gather_utilization": "higher",
    "walk_issue_efficiency": "higher",
    "walk_useful_lane_fraction": "higher",
    "walk_pallas_useful_lane_fraction": "higher",
    "walk_pallas_queries_per_sec": "higher",
    "walk_pallas_speedup": "higher",
    "walk_pallas_kernel_seconds": "lower",
    "walk_pallas_stall_p99_ms": "lower",
    # the build family (pipelined + delta builds, ROADMAP item 1):
    # build rates and the delta-vs-full ratio improve UP, pipeline
    # stall improves DOWN — and staging OVERLAP improves UP despite
    # its _seconds suffix (overlap won is host work hidden behind the
    # device, exactly what the pipeline exists for), so it MUST be
    # listed here or the suffix heuristic gates it backwards
    "scale_build_rows_per_sec": "higher",
    "road_tpu_build_rows_per_sec": "higher",
    "build_delta_vs_full_ratio": "higher",
    "build_full_rows_per_sec": "higher",
    "build_delta_rows_per_sec": "higher",
    "build_pipeline_stall_seconds": "lower",
    "build_stage_overlap_seconds": "higher",
    # the worker-mesh family (multi-device sharded execution): per-
    # device-count rates improve UP, the strong-scaling overhead split
    # improves DOWN, and the multichip smoke is a 0/1 health bit whose
    # only regression is 1 -> 0 (tolerance 0 below). The
    # shard_strong_scaling_* scalars pin the PR 13 headline: the W=8
    # rate regressing vs W=1 was the bug this family measures.
    "mesh_build_rows_per_sec_d8": "higher",
    "mesh_walk_queries_per_sec_d8": "higher",
    "mesh_mat_rows_per_sec_d8": "higher",
    "shard_strong_scaling_rows_per_sec_w1": "higher",
    "shard_strong_scaling_rows_per_sec_w8": "higher",
    "shard_strong_scaling_overhead_w8_seconds": "lower",
    "multichip_smoke_ok": "higher",
    # the compressed-residency family (RLE/pack4 resident CPD shards,
    # ROADMAP item 1): the resident-bytes ratio and compressed walk
    # rates improve UP, the per-batch decompress overhead improves
    # DOWN (its _seconds suffix would catch it — listed so the
    # family's contract is in one place like the others)
    "cpd_resident_bytes_ratio": "higher",
    "compressed_walk_queries_per_sec": "higher",
    "compressed_raw_walk_queries_per_sec": "higher",
    "compressed_vs_raw_walk_ratio": "higher",
    "compressed_decompress_seconds": "lower",
    # the streaming-transport family (RPC vs FIFO head-to-head on the
    # same workload): the dispatch-overhead ratio improves UP (fifo
    # per-batch cost / rpc per-batch cost), per-batch overheads and
    # tail latency improve DOWN (the _ms suffix would catch those —
    # listed so the family's contract is in one place like the others)
    "serve_rpc_vs_fifo_dispatch_ratio": "higher",
    "serve_rpc_dispatch_ms": "lower",
    "serve_fifo_dispatch_ms": "lower",
    "serve_rpc_p99_ms": "lower",
    "serve_fifo_p99_ms": "lower",
    "serve_rpc_queries_per_sec": "higher",
    "serve_fifo_queries_per_sec": "higher",
    # the telemetry family (fleet telemetry bus, PR 16): the head's
    # ingest rate improves UP; the publish tail and the overhead
    # fraction (mean tick build time / publish interval — the "< 1%
    # of serve throughput" acceptance) improve DOWN (the p99_ms suffix
    # would catch the first — listed so the family's contract is in
    # one place like the others)
    "telemetry_head_ingest_per_sec": "higher",
    "telemetry_publish_p99_ms": "lower",
    "telemetry_publish_overhead_frac": "lower",
    # the closed-loop control family (policy daemon, PR 17): both arms'
    # time-to-recover and shed rate improve DOWN — shed_rate defeats
    # the suffix heuristic (no _ms/_seconds), and the policy-off
    # baselines gate too so a regression in the daemon-off recovery
    # path (supervisor backoff, breaker heal) cannot hide behind the
    # policy-on deltas
    "control_recover_seconds": "lower",
    "control_shed_rate": "lower",
    "control_p99_ms": "lower",
    "control_off_recover_seconds": "lower",
    "control_off_shed_rate": "lower",
    "control_off_p99_ms": "lower",
    # the gateway family (N-replica tier vs the single head, PR 18):
    # aggregate throughput, the tier-vs-head ratio, answer bit-identity
    # (a 0/1 health bit), and both cache-plane hit rates improve UP;
    # per-frontend fairness is a max/min q/s ratio whose ideal is 1.0,
    # so it improves DOWN (no suffix catches it — listed like the
    # other family contracts, in one place)
    "gateway_aggregate_queries_per_sec": "higher",
    "gateway_single_head_queries_per_sec": "higher",
    "gateway_vs_single_head_ratio": "higher",
    "gateway_fairness_ratio": "lower",
    "gateway_answers_match": "higher",
    "gateway_fleet_cache_hit_rate": "higher",
    "gateway_single_head_cache_hit_rate": "higher",
    # the gateway HA family (leased discovery + failover, PR 19): lost
    # requests and duplicate answers are correctness counts whose ideal
    # is 0, failover recovery time improves DOWN like any latency
    "gateway_ha_lost_requests": "lower",
    "gateway_ha_duplicate_answers": "lower",
    "gateway_ha_failover_p99_ms": "lower",
    # the answer-integrity family (scrub + audit + fingerprints,
    # PR 20): divergences on a clean run and corrupted answers served
    # in the drill are correctness counts whose ideal is 0; the
    # audit/scrub overhead fractions (1 - audited q/s / baseline q/s)
    # and the corrupt-resident detection latency improve DOWN; the
    # throughput columns improve UP like any q/s
    "integrity_audit_divergence": "lower",
    "integrity_wrong_answers_served": "lower",
    "integrity_audit_overhead_frac": "lower",
    "integrity_scrub_overhead_frac": "lower",
    "integrity_detect_seconds": "lower",
    "integrity_base_queries_per_sec": "higher",
    "integrity_audit1_queries_per_sec": "higher",
    "integrity_audit10_queries_per_sec": "higher",
    "integrity_scrub_queries_per_sec": "higher",
}

#: per-key default tolerances (CLI --key-tolerance still overrides):
#: lane/utilization fractions are stable kernel properties — a real
#: regression there is structural, so gate them tighter than raw
#: throughput
_KEY_TOLERANCES = {
    "walk_useful_lane_fraction": 0.15,
    "walk_pallas_useful_lane_fraction": 0.15,
    "walk_gather_utilization": 0.15,
    "walk_issue_efficiency": 0.15,
    # the delta-vs-full ratio is a structural property of the dirty-set
    # pass (work skipped / work done), not a raw device timing — a real
    # drop means the pass stopped skipping, so gate it tighter than the
    # default
    "build_delta_vs_full_ratio": 0.2,
    # the multichip smoke is pass/fail: ANY drop (1 -> 0) gates
    "multichip_smoke_ok": 0.0,
    # the resident-bytes ratio is a structural property of the codec
    # on a fixed synthetic graph (bytes in / bytes out), not a timing
    # — a real drop means the encoder stopped compressing
    "cpd_resident_bytes_ratio": 0.15,
    # the rpc-vs-fifo dispatch ratio measures transport overhead
    # (subprocess + files + FIFO rendezvous vs one socket round-trip)
    # on the SAME engine and workload; it sits far above 1 and jitter
    # affects both lanes alike, but the FIFO lane's bash-subprocess
    # cost swings with host load — gate it loosely (a real regression
    # to ~1 still trips)
    "serve_rpc_vs_fifo_dispatch_ratio": 0.5,
    # tick build cost is microseconds measured against host jitter —
    # the p99 and the derived overhead fraction both swing with host
    # load, so gate them loosely (a real
    # regression — publish cost approaching the interval — still
    # trips); the ingest rate is in-process dict work, same story
    "telemetry_publish_p99_ms": 0.5,
    "telemetry_publish_overhead_frac": 0.5,
    "telemetry_head_ingest_per_sec": 0.5,
    # recovery timings are dominated by backoff/probe cadences racing
    # host scheduling jitter; shed rates depend on exactly how many
    # requests land inside the outage window — gate all four loosely
    # (a real regression, e.g. re-admission stops happening, blows far
    # past 2x)
    "control_recover_seconds": 0.5,
    "control_shed_rate": 0.5,
    "control_off_recover_seconds": 0.5,
    "control_off_shed_rate": 0.5,
    "control_p99_ms": 0.5,
    "control_off_p99_ms": 0.5,
    # answer bit-identity between the gateway tier and the single-head
    # line protocol is pass/fail: ANY drop (1 -> 0) gates
    "gateway_answers_match": 0.0,
    # hit rates on the fixed zipf pool are structural cache properties
    # (keyspace skew / capacity), not timings — gate tighter than the
    # throughput default
    "gateway_fleet_cache_hit_rate": 0.2,
    "gateway_single_head_cache_hit_rate": 0.2,
    # tier throughput and fairness race thread scheduling on a shared
    # host — gate loosely (a real regression, e.g. one replica starved
    # to a halt, blows far past 2x)
    "gateway_aggregate_queries_per_sec": 0.5,
    "gateway_single_head_queries_per_sec": 0.5,
    "gateway_vs_single_head_ratio": 0.5,
    "gateway_fairness_ratio": 0.5,
    # HA drill correctness is absolute: losing ANY accepted request or
    # double-booking ANY answer across a failover gates at zero
    "gateway_ha_lost_requests": 0.0,
    "gateway_ha_duplicate_answers": 0.0,
    # failover latency is bounded by the lease TTL racing thread
    # scheduling on a shared host — gate loosely (a real regression,
    # e.g. failover stops working and waits burn their full deadline,
    # blows far past 2x)
    "gateway_ha_failover_p99_ms": 1.0,
    # integrity correctness is absolute: an audit divergence on an
    # uncorrupted run, or ANY corrupted answer reaching a client in
    # the drill, gates at zero
    "integrity_audit_divergence": 0.0,
    "integrity_wrong_answers_served": 0.0,
    # overhead fractions compare two q/s measurements racing host
    # jitter (both near the noise floor at 1 per mille), and detection
    # latency is a poll-cadence race — gate all three loosely; the
    # raw q/s columns inherit the same story
    "integrity_audit_overhead_frac": 1.0,
    "integrity_scrub_overhead_frac": 1.0,
    "integrity_detect_seconds": 0.5,
    "integrity_base_queries_per_sec": 0.5,
    "integrity_audit1_queries_per_sec": 0.5,
    "integrity_audit10_queries_per_sec": 0.5,
    "integrity_scrub_queries_per_sec": 0.5,
}


def find_bench_records(dirname: str) -> list[str]:
    """``BENCH_r*.json`` sorted by round number."""
    paths = glob.glob(os.path.join(dirname, "BENCH_r[0-9]*.json"))
    def _round(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1
    return sorted((p for p in paths if _round(p) >= 0), key=_round)


def bench_numbers(path: str) -> dict[str, float]:
    """The comparable scalar metrics of one bench record: the headline
    value plus every numeric entry of ``parsed.headline`` (the driver's
    record format; a raw bench payload's top-level ``value``/
    ``detail`` also works). A record whose ``parsed`` is null (the r04
    overflow failure mode) falls back to the last JSON object in its
    stdout ``tail``; records with no numbers at all yield ``{}`` —
    the CLI then walks further back for a comparable round."""
    with open(path) as f:
        doc = json.load(f)
    parsed = doc.get("parsed") or doc
    if not isinstance(parsed, dict) or (
            "parsed" in doc and doc["parsed"] is None):
        parsed = None
        tail = doc.get("tail", "")
        if isinstance(tail, str):
            start = tail.rfind('\n{"metric"')
            if start < 0 and tail.startswith('{"metric"'):
                start = -1      # tail IS the line
            try:
                parsed = json.loads(tail[start + 1:])
            except ValueError:
                parsed = None
    if not isinstance(parsed, dict):
        return {}
    out: dict[str, float] = {}
    if isinstance(parsed.get("value"), (int, float)):
        out[parsed.get("metric", "value")] = float(parsed["value"])
    headline = parsed.get("headline") or parsed.get("detail") or {}
    for k, v in headline.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = float(v)
    return out


#: recorded per-key baseline waivers live next to the BENCH_r*.json
#: history (checked into the repo, so the acceptance is reviewable)
WAIVER_FILE = "BENCH_WAIVERS.json"


def bench_round(path: str) -> str:
    """``BENCH_r05.json`` -> ``"r05"`` (empty for non-canonical
    names — explicit OLD NEW paths can be anything)."""
    m = re.search(r"BENCH_(r\d+)\.json$", os.path.basename(path))
    return m.group(1) if m else ""


def load_waivers(dirname: str) -> dict:
    """The recorded waiver map ``{key: {"round": "rNN", ...}}``; absent
    or unreadable file = no waivers (logged — a corrupt waiver file
    must fail toward GATING, never toward silently passing). Unknown
    per-entry keys are tolerated (the annotation contract of every
    other on-disk codec here)."""
    path = os.path.join(dirname, WAIVER_FILE)
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        return {}
    except ValueError as e:
        log.error("unreadable %s: %s (treating as NO waivers)", path, e)
        return {}
    return doc if isinstance(doc, dict) else {}


def record_waiver(dirname: str, key: str, round_name: str,
                  entry: dict | None = None) -> dict:
    """Merge one waiver into the recorded file (atomic write) and
    return the updated map. ``entry`` carries the context a reviewer
    needs (old/new values, reason)."""
    from ..utils.atomicio import atomic_write_bytes

    waivers = load_waivers(dirname)
    rec = {"round": round_name}
    if entry:
        rec.update(entry)
    waivers[key] = rec
    atomic_write_bytes(
        os.path.join(dirname, WAIVER_FILE),
        (json.dumps(waivers, indent=1, sort_keys=True) + "\n").encode())
    return waivers


def compare_bench(old_path: str, new_path: str,
                  tolerance: float = DEFAULT_TOLERANCE,
                  key_tolerances: dict[str, float] | None = None,
                  waivers: dict | None = None) -> dict:
    """Per-key regression check; returns ``{"regressions": [...],
    "improved": [...], "waived": [...], "checked": N, ...}``. A key
    present only on one side is skipped (workloads grow across rounds;
    absence is not a regression). A regression whose key carries a
    recorded waiver FOR THE NEW ROUND moves to ``waived`` instead — the
    waiver is a per-round baseline acceptance, so a fresh regression in
    a later round gates again."""
    old = bench_numbers(old_path)
    new = bench_numbers(new_path)
    key_tolerances = key_tolerances or {}
    waivers = waivers or {}
    new_round = bench_round(new_path)
    regressions, improved, waived, checked = [], [], [], []
    for key in sorted(set(old) & set(new)):
        tol = key_tolerances.get(
            key, _KEY_TOLERANCES.get(key, tolerance))
        ov, nv = old[key], new[key]
        checked.append(key)
        if ov == 0:
            continue
        direction = _KEY_DIRECTIONS.get(key)
        lower_better = (direction == "lower" if direction
                        else bool(_LOWER_BETTER.search(key)))
        ratio = nv / ov
        entry = {"key": key, "old": ov, "new": nv,
                 "ratio": round(ratio, 3), "tolerance": tol,
                 "direction": "lower" if lower_better else "higher"}
        if lower_better:
            regressed = ratio > 1.0 + tol
            better = ratio < 1.0
        else:
            regressed = ratio < 1.0 - tol
            better = ratio > 1.0
        if regressed:
            waiver = waivers.get(key)
            if (isinstance(waiver, dict) and new_round
                    and waiver.get("round") == new_round):
                entry["waiver"] = waiver
                waived.append(entry)
            else:
                regressions.append(entry)
        elif better:
            improved.append(entry)
    return {"old": os.path.basename(old_path),
            "new": os.path.basename(new_path),
            "checked": len(checked), "regressions": regressions,
            "improved": improved, "waived": waived}
