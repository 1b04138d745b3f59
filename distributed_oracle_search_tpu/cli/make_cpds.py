"""Distributed CPD precompute launcher: the framework's ``make_cpds.py``.

Role parity with reference P2 (SURVEY.md §2.1): read the cluster conf, then
for each worker start the per-worker CPD build.

* ``partmethod=tpu`` (the north-star path): no ssh at all — one in-process
  sharded build over the device mesh (every mesh shard builds its rows in
  parallel, SURVEY.md §2.3 "build parallelism"), then the index is saved to
  ``outdir`` with its manifest.
* host partmethods (``div``/``mod``/``alloc``): launch one
  ``worker.build`` process per worker — ssh + detached tmux for remote
  hosts (the reference's mechanism, ``make_cpds.py:21``), tracked local
  subprocesses for localhost. Unlike the reference's fire-and-forget
  (SURVEY.md §3.1 "no completion signal"), local builds are awaited and the
  index manifest is written when all shards are present.

``-t`` runs the canned smoke config; ``-w N`` restricts to one worker
(reference ``make_cpds.py:27-41,58-62``). ``--verify`` runs a
check-only integrity pass over the conf's index instead of building
(exit 0/3/4 clean/degraded/corrupt); ``--scrub`` repeats that pass on
a cadence (``--scrub-interval``/``--scrub-passes``) and exits with the
worst code seen — the at-rest counterpart of the serve-side resident
scrubber; ``--no-resume`` disables the
ledger-based crash-resume (on by default). ``--delta-from OLD --diff
FUSED`` runs a DELTA rebuild: only rows the fused diff's changed edges
can affect are recomputed, untouched blocks byte-copy, and the result
lands as an epoch-tagged index (``OLD/epoch-e<N>``) the serve path can
promote without restart.
"""

from __future__ import annotations

import json
import os
import sys

from .args import parse_args
from ..transport.launch import launch, session_name
from ..utils.atomicio import sweep_stale_artifacts
from ..utils.compile_cache import use_compile_cache
from ..utils.config import ClusterConfig, test_config, test_worker_count
from ..utils.log import get_logger, set_verbosity

log = get_logger(__name__)


def worker_build_cmd(wid: int, conf: ClusterConfig, chunk: int = 0,
                     engine: str = "python",
                     resume: bool = True,
                     codec: str | None = None) -> str:
    """The shell command a host-mode worker runs (our ``make_cpd_auto``)."""
    partkey = (" ".join(str(b) for b in conf.partkey)
               if isinstance(conf.partkey, (list, tuple))
               else str(conf.partkey))
    if engine == "native":
        from ..utils.nativebin import require_binary
        if chunk:
            log.warning("--chunk is a JAX-builder staging knob; the native "
                        "builder works block-by-block and ignores it")
        if codec:
            log.warning("--codec is a JAX-builder knob; the native "
                        "builder writes raw blocks and ignores it")
        return (f"{require_binary('make_cpd_auto')}"
                f" --input {conf.xy_file} --partmethod {conf.partmethod}"
                f" --partkey {partkey} --workerid {wid}"
                f" --maxworker {conf.maxworker} --outdir {conf.outdir}")
    cmd = (f"{sys.executable} -m distributed_oracle_search_tpu.worker.build"
           f" --input {conf.xy_file} --partmethod {conf.partmethod}"
           f" --partkey {partkey} --workerid {wid}"
           f" --maxworker {conf.maxworker} --outdir {conf.outdir}")
    if chunk:
        cmd += f" --chunk {chunk}"
    if not resume:
        cmd += " --no-resume"
    if codec:
        cmd += f" --codec {codec}"
    repl = conf.effective_replication()
    if repl > 1:
        cmd += f" --replication {repl}"
    return cmd


def call_worker(wid: int, conf: ClusterConfig, chunk: int = 0,
                engine: str = "python", resume: bool = True,
                codec: str | None = None):
    """Launch one worker's build (parity: reference ``make_cpds.py:10-25``).

    Returns a Popen handle when the build runs as a tracked local
    subprocess, else None (tmux/ssh detached)."""
    host = conf.workers[wid]
    cmd = worker_build_cmd(wid, conf, chunk, engine, resume=resume,
                           codec=codec)
    log.info("launch build w%d on %s: %s", wid, host, cmd)
    # prefer_track: builds are finite jobs — await local ones so the index
    # manifest can be finalized when they all complete
    return launch(host, session_name("worker", wid), cmd,
                  projectdir=conf.projectdir, prefer_track=True)


def run_verify(conf: ClusterConfig) -> int:
    """Check-only integrity pass: digest/shape-verify every manifest
    block in place, print the report, exit 0/3/4 (clean / degraded /
    corrupt — ``process_query``'s convention)."""
    from ..data.formats import xy_node_count
    from ..models.cpd import read_manifest, verify_index, verify_exit_code
    from ..parallel.partition import DistributionController

    # verify against the manifest's own block_size and replication (a
    # worker.build --block-size or replicated index is still a valid
    # index); the partition quadruple is still cross-checked against
    # the conf
    dc_kw = {}
    try:
        man = read_manifest(conf.outdir)
        bs = int(man.get("block_size", 0))
        if bs > 0:
            dc_kw["block_size"] = bs
        repl = int(man.get("replication", 1))
        if repl > 1:
            dc_kw["replication"] = repl
    except (OSError, ValueError):
        pass            # verify_index will report the unusable manifest
    try:
        dc = DistributionController(conf.partmethod, conf.partkey,
                                    conf.maxworker,
                                    xy_node_count(conf.xy_file), **dc_kw)
    except ValueError as e:
        # e.g. the manifest records replication > this conf's
        # maxworker: a manifest/conf mismatch is the contract's exit 4
        # (fatal), never a traceback
        log.error("verify fatal: %s", e)
        print(json.dumps({"index": conf.outdir, "exit_code": 4,
                          "fatal": str(e)}))
        return 4
    report = verify_index(conf.outdir, dc=dc)
    for fname in report["missing"]:
        log.error("missing block: %s", fname)
    for ent in report["corrupt"]:
        log.error("corrupt block: %s (%s)", ent["file"], ent["reason"])
    if report.get("fatal"):
        log.error("verify fatal: %s", report["fatal"])
    code = verify_exit_code(report)
    print(json.dumps({"index": conf.outdir, "exit_code": code,
                      **{k: report[k] for k in
                         ("total", "ok", "unverified", "missing",
                          "corrupt")},
                      **({"fatal": report["fatal"]}
                         if report.get("fatal") else {})}))
    return code


def run_scrub(conf: ClusterConfig, args) -> int:
    """``--scrub``: repeat the ``--verify`` check-only pass on a
    cadence and exit with the WORST code any pass produced (0 clean /
    3 degraded / 4 corrupt — degradation seen once is degradation,
    even if a later pass healed it out of view). ``--scrub-passes 0``
    repeats until interrupted; the interrupt still reports honestly."""
    import time

    worst = passes = 0
    budget = max(0, int(getattr(args, "scrub_passes", 1)))
    try:
        while True:
            worst = max(worst, run_verify(conf))
            passes += 1
            log.info("scrub pass %d done (worst exit so far: %d)",
                     passes, worst)
            if budget and passes >= budget:
                break
            time.sleep(max(0.0, float(getattr(args, "scrub_interval",
                                              60.0))))
    except KeyboardInterrupt:
        log.info("scrub interrupted after %d pass(es)", passes)
    return worst


def run_delta(conf: ClusterConfig, args) -> int:
    """Delta rebuild (``--delta-from OLD_INDEX --diff FUSED``): old
    index + fused diff epoch → a new epoch-tagged index bit-identical
    to a from-scratch build on the retimed graph, recomputing only the
    rows the changed edges can affect (``models.cpd.delta_build_index``
    — untouched blocks byte-copy with their journaled digests). Exit 0
    on success, 4 when the old index is unusable."""
    from ..data.graph import Graph
    from ..models.cpd import delta_build_index, read_manifest
    from ..parallel.partition import DistributionController

    if not args.diff:
        log.error("--delta-from needs the fused diff file (--diff)")
        return 2
    # honor the old manifest's block_size/replication like --verify (a
    # worker.build --block-size index delta-rebuilds consistently)
    dc_kw = {}
    try:
        man = read_manifest(args.delta_from)
        bs = int(man.get("block_size", 0))
        if bs > 0:
            dc_kw["block_size"] = bs
        repl = int(man.get("replication", 1))
        if repl > 1:
            dc_kw["replication"] = repl
    except (OSError, ValueError) as e:
        log.error("delta fatal: no readable manifest in %s: %s",
                  args.delta_from, e)
        print(json.dumps({"index": args.delta_from, "exit_code": 4,
                          "fatal": str(e)}))
        return 4
    graph = Graph.from_xy(conf.xy_file)
    dc = DistributionController(conf.partmethod, conf.partkey,
                                conf.maxworker, graph.n, **dc_kw)
    report = delta_build_index(
        graph, dc, args.delta_from, args.diff,
        epoch=getattr(args, "delta_epoch", None), chunk=args.chunk,
        resume=not getattr(args, "no_resume", False))
    print(json.dumps({"exit_code": 0, **report}))
    return 0


def run_tpu(conf: ClusterConfig, args) -> None:
    """In-process sharded build over the mesh."""
    from ..parallel.multihost import initialize_from_conf
    initialize_from_conf(conf)

    import jax
    if jax.process_count() == 1:
        # debris from killed builds; skipped multi-controller (another
        # process may have an atomic write in flight in the shared dir)
        sweep_stale_artifacts(conf.outdir)

    from ..data.graph import Graph
    from ..models.cpd import CPDOracle
    from ..parallel.mesh import mesh_from_config
    from ..parallel.partition import DistributionController

    graph = Graph.from_xy(conf.xy_file)
    dc = DistributionController(conf.partmethod, conf.partkey,
                                conf.maxworker, graph.n)
    mesh = mesh_from_config(conf)
    oracle = CPDOracle(graph, dc, mesh=mesh)
    oracle.build(chunk=args.chunk)
    oracle.save(conf.outdir, codec=getattr(args, "codec", None))
    # which device holds each worker's rows: a mesh that silently put
    # every shard on one device would still answer correctly
    shard_devices = {
        int(s.index[0].start or 0): s.device.id
        for s in oracle.fm.addressable_shards}
    dev = jax.devices()[0]
    print(json.dumps({
        "built": conf.outdir, "nodes": int(graph.n),
        "shards": conf.maxworker, "shard_devices": shard_devices,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}))


def run_host(conf: ClusterConfig, args) -> None:
    # sweep BEFORE any worker launches: once builds are running, their
    # own in-flight *.tmp files must not be swept out from under them
    sweep_stale_artifacts(conf.outdir)
    resume = not getattr(args, "no_resume", False)
    procs = []
    for wid in range(conf.maxworker):
        if args.worker != -1 and wid != args.worker:
            continue
        proc = call_worker(wid, conf, chunk=args.chunk, engine=args.engine,
                           resume=resume,
                           codec=getattr(args, "codec", None))
        if proc is not None:
            procs.append((wid, proc))
    failures = 0
    for wid, proc in procs:
        if proc.wait() != 0:
            log.error("worker %d build failed (rc=%d)", wid, proc.returncode)
            failures += 1
    if procs and not failures and args.worker == -1:
        # all local builds done -> finalize the index manifest
        from ..data.formats import xy_node_count
        from ..models.cpd import (
            anti_entropy, build_replica_shards, write_index_manifest,
        )
        from ..parallel.partition import DistributionController
        dc = DistributionController(conf.partmethod, conf.partkey,
                                    conf.maxworker,
                                    xy_node_count(conf.xy_file),
                                    replication=conf
                                    .effective_replication())
        graph = None
        if dc.replication > 1:
            # backstop for builders that only emit primaries (the
            # native engine, or replica builds that raced a peer's
            # primary): materialize replica sets with files still
            # MISSING on disk (existence scan only — the workers'
            # ledgers already digest-verified what they wrote, and the
            # anti-entropy pass below digest-checks everything once)
            from ..models.cpd import shard_block_name
            from ..data.graph import Graph as _Graph
            graph = _Graph.from_xy(conf.xy_file)
            bs = dc.block_size
            for host in range(conf.maxworker):
                missing = any(
                    not os.path.exists(os.path.join(
                        conf.outdir,
                        shard_block_name(shard, bid,
                                         dc.replica_rank(shard, host))))
                    for shard in dc.replica_shards(host)[1:]
                    for bid in range((dc.n_owned(shard) + bs - 1) // bs))
                if missing:
                    build_replica_shards(graph, dc, host, conf.outdir,
                                         chunk=args.chunk)
        manifest = write_index_manifest(conf.outdir, dc)
        if dc.replication > 1:
            report = anti_entropy(conf.outdir, dc, graph=graph,
                                  manifest=manifest)
            print(f"anti-entropy: {report['checked']} replica "
                  f"block(s) cross-checked, "
                  f"{len(report['mismatched'])} divergent, "
                  f"{len(report['healed'])} healed")
        print(f"index complete -> {conf.outdir}")
    if failures:
        raise SystemExit(f"{failures} worker build(s) failed")


def main(argv=None) -> int:
    args = parse_args(argv, prog="make_cpds")
    set_verbosity(args.verbose)
    use_compile_cache()
    if args.test:
        from ..data.synth import ensure_synth_dataset

        # sized like process_query's test mode — the two must build/read
        # the same index
        conf = test_config(n_workers=test_worker_count(args.backend))
        ensure_synth_dataset(os.path.dirname(conf.xy_file) or "./data")
    else:
        conf = ClusterConfig.load(args.c)
    if getattr(args, "scrub", False):
        return run_scrub(conf, args)
    if getattr(args, "verify", False):
        return run_verify(conf)
    if getattr(args, "delta_from", None):
        return run_delta(conf, args)
    if args.backend == "tpu" or (args.backend == "auto" and conf.is_tpu):
        run_tpu(conf, args)
    else:
        run_host(conf, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
