"""Per-worker CPD build program: the framework's ``make_cpd_auto``.

CLI parity with reference C1 (SURVEY.md §2.2; invoked at reference
``make_cpds.py:20``)::

    python -m distributed_oracle_search_tpu.worker.build \
        --input <xy> --partmethod <div|mod|alloc|tpu> --partkey <int...> \
        --workerid <int> --maxworker <int> [--outdir <dir>] [--chunk N]

Computes the first-move rows for the node subset owned by ``workerid`` —
the reference runs one Dijkstra sweep per owned node over all OpenMP cores
(reference ``README.md:95``); here the whole shard is built by the batched
min-plus kernel on the local accelerator — and writes one ``.npy`` per
block (``bid``/``bidx`` scheme of the distribution controller). Re-running
resumes at block granularity.
"""

from __future__ import annotations

import argparse
import os
import sys

from ..data.graph import Graph
from ..models.cpd import build_worker_shard
from ..parallel.partition import DistributionController
from ..utils.compile_cache import use_compile_cache
from ..utils.log import get_logger, set_verbosity

log = get_logger(__name__)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--input", required=True, help="graph .xy file")
    p.add_argument("--partmethod", required=True,
                   choices=["div", "mod", "alloc", "tpu"])
    p.add_argument("--partkey", type=int, nargs="+", default=[1])
    p.add_argument("--workerid", type=int, required=True)
    p.add_argument("--maxworker", type=int, required=True)
    p.add_argument("--outdir", default=None,
                   help="default: the input file's directory "
                        "(reference README.md:93)")
    p.add_argument("--chunk", type=int, default=0,
                   help="build-step rows (0 = whole shard at once)")
    p.add_argument("--block-size", type=int, default=0,
                   help="rows per block FILE (0 = the controller "
                        "default, which is what the serving CLIs "
                        "expect; the manifest records the value and "
                        "make_cpds --verify honors it — non-default "
                        "sizes are for tooling/chaos tests whose "
                        "consumers build a matching controller)")
    p.add_argument("--method", default="auto",
                   choices=["auto", "sweep", "shift", "frontier",
                            "ellsplit", "ell"],
                   help="relaxation kernel: fast-sweeping grid scans, "
                        "gather-free shift path, delta-stepping frontier "
                        "queue, ELL+COO split (degree-skewed graphs), "
                        "padded-ELL gather, or auto by structure gates "
                        "(models.cpd.pick_build_kernel)")
    p.add_argument("--no-resume", action="store_true",
                   help="rebuild every block from scratch (default: "
                        "resume — skip blocks the build ledger records "
                        "as complete with a matching on-disk digest)")
    p.add_argument("--adopt-shard", type=int, default=None,
                   metavar="SHARD",
                   help="membership catch-up mode: instead of building "
                        "this worker's own rows, digest-verify (and "
                        "heal via the copy/rebuild path) the named "
                        "shard's primary block set — what a joining "
                        "worker runs before the reconfiguration "
                        "controller commits the epoch bump. Idempotent "
                        "and crash-resumable (build-ledger journaled)")
    p.add_argument("--replication", type=int, default=None,
                   help="R-way shard replication: after the primary "
                        "rows, also build this worker's hosted replica "
                        "block sets (rank r of shard (wid - r) %% W; "
                        "copied from digest-valid primaries when "
                        "sharing a filesystem, recomputed otherwise). "
                        "Default: DOS_REPLICATION or 1")
    p.add_argument("--codec", default=None,
                   choices=["raw", "pack4", "rle", "auto"],
                   help="persist blocks compressed (models.resident "
                        "RLE/pack4 containers; per-block degrade to "
                        "raw when not viable). Default: the "
                        "DOS_CPD_RESIDENT knob (raw = legacy format)")
    p.add_argument("--metrics-dump", default="",
                   help="write a JSON obs-metrics snapshot here on exit "
                        "(build_blocks_resumed_total etc.)")
    p.add_argument("-v", "--verbose", action="count", default=0)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    set_verbosity(args.verbose)
    use_compile_cache()
    outdir = args.outdir or os.path.dirname(os.path.abspath(args.input))
    partkey = args.partkey if args.partmethod == "alloc" else args.partkey[0]

    from ..utils.env import env_cast

    replication = args.replication
    if replication is None:
        replication = env_cast("DOS_REPLICATION", 1, int)
    if not 1 <= replication <= args.maxworker:
        # env policy: degrade, don't crash — and match the head, which
        # ignores an out-of-range DOS_REPLICATION the same way
        # (ClusterConfig.effective_replication)
        log.warning("ignoring replication=%d outside [1, maxworker=%d]"
                    "; building primaries only", replication,
                    args.maxworker)
        replication = 1
    graph = Graph.from_xy(args.input)
    dc_kw = ({"block_size": args.block_size} if args.block_size > 0
             else {})
    dc = DistributionController(args.partmethod, partkey, args.maxworker,
                                graph.n, replication=replication,
                                **dc_kw)
    if args.adopt_shard is not None:
        from ..models.cpd import adopt_shard_blocks

        report = adopt_shard_blocks(graph, dc, args.adopt_shard, outdir)
        log.info("worker %d: adopted shard %d (%d block(s): %d ok, "
                 "%d unverified, %d healed)", args.workerid,
                 args.adopt_shard, report["blocks"], report["ok"],
                 report["unverified"], len(report["healed"]))
        print(f"worker {args.workerid}: adopted shard "
              f"{args.adopt_shard} ({report['blocks']} block(s), "
              f"{len(report['healed'])} healed) -> {outdir}")
        if args.metrics_dump:
            from ..obs import metrics as obs_metrics

            obs_metrics.REGISTRY.dump_json(args.metrics_dump)
        return 0
    written = build_worker_shard(graph, dc, args.workerid, outdir,
                                 chunk=args.chunk,
                                 resume=not args.no_resume,
                                 method=args.method, codec=args.codec)
    n_replica = 0
    if dc.replication > 1:
        from ..models.cpd import build_replica_shards

        replica_written = build_replica_shards(
            graph, dc, args.workerid, outdir, chunk=args.chunk,
            resume=not args.no_resume, method=args.method)
        n_replica = sum(len(v) for v in replica_written.values())
    log.info("worker %d: wrote %d primary block(s)%s to %s",
             args.workerid, len(written),
             f" + {n_replica} replica block(s)" if n_replica else "",
             outdir)
    print(f"worker {args.workerid}: {len(written)} block(s)"
          + (f" + {n_replica} replica block(s)" if dc.replication > 1
             else "") + f" -> {outdir}")
    if args.metrics_dump:
        from ..obs import metrics as obs_metrics

        obs_metrics.REGISTRY.dump_json(args.metrics_dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
