"""Headline benchmark: whole-scenario query throughput on the CPD oracle.

Mirrors the reference's headline workload (BASELINE.md): build the CPD for a
city-scale road network, then answer an entire scenario file of s–t queries.
The north-star target is "every query in full.scen answered in < 1 s"
(BASELINE.json): ``vs_baseline`` reports target_time / measured_time for the
scenario phase, so > 1.0 means beating the target.

The reference's own data files are absent from its snapshot, so the workload
is a deterministic synthetic city of comparable structure (two-way street
grid + arterials; see ``data/synth.py``). Sections (env-gated):

  main       96x96 city (9.2k nodes): build + walk/diff/dist campaigns,
             bulk-dist round, native astar/ch + device A* family rates
  table      pointer-doubling amortization path, measured break-even
                                                      (BENCH_TABLE=0 skips)
  scale      320x320 city (102,400 nodes), single chip: one full worker
             shard built with the fast-sweeping kernel, then streamed
             row-chunk serving from the on-disk index — cold round plus
             the cache-warm steady state              (BENCH_SCALE=0 skips)
  road       264k-node non-grid network: frontier build vs CPU Dijkstra,
             streamed/resident serving, free-flow AND congestion-diff
             rounds                                   (BENCH_ROAD=0 skips)
  compressed RLE/pack4 compressed-RESIDENT shard on the road rows
             (DOS_CPD_RESIDENT, models.resident): resident-bytes ratio,
             decompress-at-use walk q/s vs the raw-resident walk, and
             the per-batch decompress overhead — rides inside the road
             section                            (BENCH_COMPRESSED=0 skips)
  weak       build-time scaling over a virtual 1/2/4/8-device CPU mesh
             (subprocess), decomposed into mesh wall-clock vs per-shard
             single-device time, plus shard strong scaling on the real
             chip                                     (BENCH_WEAK=0 skips)
  serve      online serving frontend (serving/): closed-loop capacity,
             then an open-loop Poisson drill at a fraction of measured
             capacity — q/s, p50/p95/p99 latency, zipf cache hit rate,
             mean micro-batch fill                   (BENCH_SERVE=0 skips)
  gateway    rush hour on the gateway tier (gateway/): 2 binary-protocol
             frontend replicas over one worker vs the single-head line
             protocol — aggregate q/s, per-frontend fairness, fleet
             L1+L2 cache hit rate, answer parity  (BENCH_GATEWAY=0 skips)
  replication  R=2 failover drill — q/s + p99 with and without one
             killed primary (breaker forced open), plus hedge win rate
             under an injected primary delay          (BENCH_REPL=0 skips)
  reshard    elastic-membership drill — serve q/s + p99 steady vs
             through a LIVE worker join (dual-read migration window,
             epoch bump committed mid-load)        (BENCH_RESHARD=0 skips)
  traffic    live congestion plane — zipf hotspot pool served through a
             rush-hour segment replay swapping diff epochs under the
             running frontend: live-swap q/s, swap-stall p99, scoped
             cache-invalidation hit rate          (BENCH_TRAFFIC=0 skips)

All speedups are against a MEASURED native-engine run on this host's
cpu_cores core(s); *_parity_cores fields give the OpenMP core count a
linearly-scaling CPU host would need to match the TPU figure.

Roofline accounting: the walk is scalar-gather-bound, so the bench
calibrates the device's achievable gather rate with a micro-kernel of the
same shape and reports achieved vs peak (utilization) — q/s alone cannot
say whether a number is good.

Scale knobs: BENCH_WIDTH/HEIGHT, BENCH_QUERIES, BENCH_CHUNK,
BENCH_SCALE_SIDE, BENCH_SCALE_QUERIES.

Output contract (the driver captures only the LAST ~2000 stdout chars and
parses the final line as JSON — r04's single fat line outgrew that window
and the record became unparseable): stdout carries exactly ONE COMPACT
JSON line (top-line metric + headline fields, size-asserted well under
the window); the full per-section detail goes to ``BENCH_DETAIL.json``
next to this file and to stderr. Progress goes to stderr.

Every long timed section runs under a stall guard (``robust_time``):
single-shot timers are never trusted — each section is best-of-2 with
further retries while the best reading still exceeds a known-good band
from prior record captures.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def robust_time(fn, reset=None, reps: int = 2, band_s: float | None = None,
                max_reps: int = 4, label: str = "", drop_prev: bool = False):
    """Best-of-N wall-clock with stall escalation: run ``fn`` ``reps``
    times (calling ``reset`` between reps — builds resume from block
    files, so a rerun without reset would measure a no-op) and keep the
    fastest time. If a known-good ``band_s`` (from prior record captures,
    generously padded) is given and even the BEST reading exceeds it,
    keep retrying up to ``max_reps`` total — the device is stalling and
    one more reading is the only way to tell a stall from a real
    regression. ``drop_prev`` frees the held result before each rerun
    (two live copies of a device-resident result would double peak HBM);
    results here are deterministic, so the LAST run's result with the
    BEST run's time is still a faithful pair.
    Returns ``(result, best_seconds)``."""
    best = None
    out = None
    runs = 0
    while True:
        if runs:
            if drop_prev:
                out = None
            if reset is not None:
                reset()
        t0 = time.perf_counter()
        res = fn()
        dt = time.perf_counter() - t0
        runs += 1
        if drop_prev:
            out, best = res, (dt if best is None else min(best, dt))
        elif best is None or dt < best:
            best, out = dt, res
        if runs >= reps and (band_s is None or best <= band_s
                             or runs >= max_reps):
            if band_s is not None and best > band_s:
                log(f"robust_time[{label}]: best {best:.1f}s still above "
                    f"band {band_s:.1f}s after {runs} reps — reporting "
                    "it, but treat as possibly stalled")
            return out, best


def _calibrate_gather(n: int, q: int, iters: int = 64):
    """Peak scalar-gather rate (elements/s) with the walk's access shape:
    a while_loop of unrolled dependent [Q]-from-[N] gathers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    a = jnp.asarray(np.random.default_rng(0).integers(0, n, n), jnp.int32)
    idx0 = jnp.asarray(np.random.default_rng(1).integers(0, n, q), jnp.int32)

    @jax.jit
    def run(idx):
        def body(st):
            i, x = st
            for _ in range(8):
                x = a[x]                      # dependent gather chain
            return i + 1, x

        return jax.lax.while_loop(lambda st: st[0] < iters, body,
                                  (jnp.int32(0), idx))[1]

    run(idx0).block_until_ready()             # compile
    t0 = time.perf_counter()
    run(idx0).block_until_ready()
    dt = time.perf_counter() - t0
    return q * 8 * iters / dt


def _calibrate_hbm(mb: int = 512):
    """Streaming HBM bandwidth (bytes/s touched) via y = x + 1."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros(mb * (1 << 20) // 4, jnp.int32)
    f = jax.jit(lambda v: v + 1)
    f(x).block_until_ready()
    t0 = time.perf_counter()
    f(x).block_until_ready()
    dt = time.perf_counter() - t0
    return 2 * x.size * 4 / dt                 # read + write


def _native_bins():
    """Build (if needed) and locate the native CPU engine — the measured
    denominator the north-star speedups are judged against (reference
    README.md:88-95: baselines must be produced by running the pipeline,
    not copied)."""
    if shutil.which("g++") is None or shutil.which("make") is None:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    bindir = os.path.join(here, "native", "build", "fast", "bin")
    try:
        subprocess.run(["make", "-C", os.path.join(here, "native"), "fast",
                        "-j4"], check=True, capture_output=True,
                       timeout=300)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        log(f"native build failed, skipping CPU baseline: {e}")
        return None
    return {n: os.path.join(bindir, n)
            for n in ("make_cpd_auto", "fifo_auto")}


def _cpu_query_campaign(bins, xy, index, scen_queries, workdir,
                        partmethod="mod", partkey=1, workerid=0,
                        maxworker=1, rounds=2, alg="table-search",
                        difffile="-"):
    """Resident ``fifo_auto`` campaign over the FIFO wire; returns the
    engine's best per-round ``t_search`` seconds (same stats field the
    reference reports, process_query.py:198-213). ``alg`` selects the
    engine family (table-search / astar / ch); ``difffile`` runs the
    round on a congestion diff, like the reference's one-round-per-diff
    campaign loop (process_query.py:178)."""
    import numpy as np

    from distributed_oracle_search_tpu.transport.wire import (
        write_query_file,
    )

    fifo = os.path.join(workdir, f"cpu-{alg}.fifo")
    proc = subprocess.Popen(
        [bins["fifo_auto"], "--input", xy, "--partmethod", partmethod,
         "--partkey", str(partkey), "--workerid", str(workerid),
         "--maxworker", str(maxworker), "--outdir", index,
         "--alg", alg, "--fifo", fifo],
        stderr=subprocess.DEVNULL)
    deadline = time.time() + 120
    while not os.path.exists(fifo):
        if time.time() > deadline:
            proc.kill()
            raise RuntimeError("fifo_auto never came up")
        time.sleep(0.1)
    qf = os.path.join(workdir, f"cpu-{alg}.query")
    write_query_file(qf, np.asarray(scen_queries))
    best = None
    try:
        for r in range(rounds):
            af = os.path.join(workdir, f"cpu-{alg}{r}.answer")
            os.mkfifo(af)
            with open(fifo, "w") as f:
                f.write('{"itrs": 1}\n' + f"{qf} {af} {difffile}\n")
            with open(af) as f:
                line = f.readline().strip()
            os.unlink(af)
            parts = line.split(",")
            assert int(parts[6]) == len(scen_queries), \
                f"CPU campaign unfinished: {line}"
            t_search = float(parts[9])
            best = t_search if best is None else min(best, t_search)
    finally:
        with open(fifo, "w") as f:
            f.write("__DOS_STOP__\n")
        proc.wait(timeout=30)
    return best


def _timed_cpu_build(bins, args: list, label: str) -> float:
    """Best-of-2 native CPD build (the reference baseline): the single
    shared core is subject to host contention like the device is to
    stalls, and a starved CPU baseline inflates every tpu_* speedup.
    ``--no-resume`` so rep 2 recomputes instead of skipping blocks."""
    _, best = robust_time(
        lambda: subprocess.run(
            [bins["make_cpd_auto"], *args, "--no-resume"],
            check=True, capture_output=True),
        label=label)
    return best


def _weak_scaling(side: int, chunk: int):
    """Build-time vs worker count on a virtual CPU mesh (subprocess so the
    TPU-pinned parent process cannot leak in). Same TOTAL rows each run.

    Two series per W, separating oversubscription from real overhead on
    this single-core host:

    * ``mesh``  — wall-clock of the W-shard shard_map build. The 8
      virtual devices time-slice ONE core, so this SUMS the shards'
      compute: flat-ish is the best case and says nothing about chips.
    * ``shard`` — wall-clock of ONE worker's rows built alone on one
      device (the per-chip unit of work). With the build's compiled HLO
      containing ZERO collectives (tests/test_cpd_model.py pins this), W
      real chips run exactly these programs concurrently, so the
      full-build time on W chips ≈ the max shard time — this is the
      device-compute decomposition VERDICT r03 asked for.
    """
    code = f"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from distributed_oracle_search_tpu.utils.compile_cache import use_compile_cache
use_compile_cache()

import numpy as np, tempfile, shutil
from distributed_oracle_search_tpu.data import synth_city_graph
from distributed_oracle_search_tpu.models.cpd import (
    CPDOracle, build_worker_shard)
from distributed_oracle_search_tpu.parallel import DistributionController
from distributed_oracle_search_tpu.parallel.mesh import make_mesh
g = synth_city_graph({side}, {side}, seed=0)
mesh_s, shard_s, shard_rows = {{}}, {{}}, {{}}
for w in (1, 2, 4, 8):
    dc = DistributionController("tpu", None, w, g.n)
    mesh = make_mesh(n_workers=w)
    o = CPDOracle(g, dc, mesh=mesh)
    o.build(chunk={chunk})                      # warm-up: compile
    o = CPDOracle(g, dc, mesh=mesh)
    t0 = time.perf_counter()
    o.build(chunk={chunk})
    jax.block_until_ready(o.fm)
    mesh_s[str(w)] = round(time.perf_counter() - t0, 3)
    # per-shard series: worker 0's rows alone on ONE device
    d = tempfile.mkdtemp()
    try:
        build_worker_shard(g, dc, 0, d, chunk={chunk})  # warm-up
        shutil.rmtree(d); os.makedirs(d)
        t0 = time.perf_counter()
        build_worker_shard(g, dc, 0, d, chunk={chunk})
        shard_s[str(w)] = round(time.perf_counter() - t0, 3)
        shard_rows[str(w)] = dc.n_owned(0)
    finally:
        shutil.rmtree(d, ignore_errors=True)
print(json.dumps({{"mesh": mesh_s, "shard": shard_s,
                   "rows": shard_rows}}))
"""
    res = subprocess.run([sys.executable, "-c", code], cwd=os.path.dirname(
        os.path.abspath(__file__)), capture_output=True, text=True,
        timeout=900)
    if res.returncode != 0:
        log(f"weak-scaling subprocess failed: {res.stderr[-500:]}")
        return {}
    return json.loads(res.stdout.strip().splitlines()[-1])


def _mesh_scaling(side: int, chunk: int):
    """Multi-device mesh execution over 1/2/4/8 virtual CPU devices
    (subprocess, like :func:`_weak_scaling`): per device count, the
    lane-mesh build rate, the lane-split engine walk rate, and the
    on-mesh collective ``mat`` rate — with every answer asserted
    bit-identical to the single-device run inside the subprocess, so
    a parity break fails the section rather than recording a lie.

    The 8 virtual devices time-slice ONE core, so these rates measure
    dispatch/partition overhead, not speedup — flat-ish series = the
    mesh machinery is roughly free, which is the most a one-core host
    can prove (the speedup claim belongs to the hardware round, same
    caveat as the weak-scaling section).
    """
    code = f"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from distributed_oracle_search_tpu.utils.compile_cache import use_compile_cache
use_compile_cache()

import numpy as np, tempfile, shutil
from distributed_oracle_search_tpu.data import (
    synth_city_graph, synth_scenario)
from distributed_oracle_search_tpu.models.cpd import (
    CPDOracle, build_worker_shard)
from distributed_oracle_search_tpu.parallel import DistributionController
from distributed_oracle_search_tpu.parallel.mesh import make_mesh
from distributed_oracle_search_tpu.transport.wire import RuntimeConfig
from distributed_oracle_search_tpu.worker.engine import ShardEngine

g = synth_city_graph({side}, {side}, seed=0)
dc = DistributionController("tpu", None, 1, g.n)
queries = synth_scenario(g.n, 8192, seed=13)
rc = RuntimeConfig()
idx = tempfile.mkdtemp()
try:
    build_worker_shard(g, dc, 0, idx, chunk={chunk})
    mat_s = int(queries[0][0])
    mat_t = np.arange(g.n)[:512]
    build_s, walk_s, mat_s_sec = {{}}, {{}}, {{}}
    walk_base = mat_base = None
    for L in (1, 2, 4, 8):
        os.environ["DOS_MESH_DEVICES"] = str(L)
        # lane-mesh build (fresh ctx per L: the lane mesh is part of it)
        ctx = {{}}
        d = tempfile.mkdtemp()
        try:
            build_worker_shard(g, dc, 0, d, chunk={chunk}, ctx=ctx)
            shutil.rmtree(d); os.makedirs(d)
            t0 = time.perf_counter()
            build_worker_shard(g, dc, 0, d, chunk={chunk},
                               resume=False, ctx=ctx)
            build_s[str(L)] = round(g.n / (time.perf_counter() - t0), 1)
        finally:
            shutil.rmtree(d, ignore_errors=True)
        # lane-split walk through the engine (est-sort + buckets + unsort)
        eng = ShardEngine(g, dc, 0, idx)
        assert eng.n_lanes == L, (eng.n_lanes, L)
        eng.answer(queries, rc)
        t0 = time.perf_counter()
        c, p, f, _st = eng.answer(queries, rc)
        walk_s[str(L)] = round(len(queries) / (time.perf_counter() - t0), 1)
        if walk_base is None:
            walk_base = (c, p, f)
        else:
            for a, b in zip(walk_base, (c, p, f)):
                np.testing.assert_array_equal(a, b)
        # on-mesh collective mat: one worker shard per device
        dcl = DistributionController("tpu", None, L, g.n)
        ol = CPDOracle(g, dcl, mesh=make_mesh(n_workers=L)).build(
            chunk={chunk})
        ol.query_mat(mat_s, mat_t)
        t0 = time.perf_counter()
        for _ in range(4):
            mc, mf = ol.query_mat(mat_s, mat_t)
        mat_s_sec[str(L)] = round(
            4 * len(mat_t) / (time.perf_counter() - t0), 1)
        if mat_base is None:
            mat_base = (mc, mf)
        else:
            np.testing.assert_array_equal(mat_base[0], mc)
            np.testing.assert_array_equal(mat_base[1], mf)
finally:
    shutil.rmtree(idx, ignore_errors=True)
print(json.dumps({{"build": build_s, "walk": walk_s,
                   "mat": mat_s_sec}}))
"""
    res = subprocess.run([sys.executable, "-c", code], cwd=os.path.dirname(
        os.path.abspath(__file__)), capture_output=True, text=True,
        timeout=1200)
    if res.returncode != 0:
        log(f"mesh-scaling subprocess failed: {res.stderr[-500:]}")
        return {}
    return json.loads(res.stdout.strip().splitlines()[-1])


def _sharded_stream(xy: str, index: str, qfile: str):
    """Two CPU-backed controller processes serve one streamed campaign
    sharded: process p streams only workers ``wid % 2 == p``. Returns
    per-process wire bytes (evidence the upload work split — the real
    multi-chip win is W host-to-device uploads running concurrently,
    which one machine cannot time honestly, so the bench records the
    byte split instead).
    """
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    code = """
import json, os, sys
xy, index, qfile, coord, pid = (sys.argv[1], sys.argv[2], sys.argv[3],
                                sys.argv[4], int(sys.argv[5]))
from distributed_oracle_search_tpu.parallel.multihost import initialize
initialize(coordinator=coord, num_processes=2, process_id=pid,
           cpu_devices_per_process=4)
import numpy as np
from distributed_oracle_search_tpu.cli.process_query import _StreamedServe
from distributed_oracle_search_tpu.data import Graph
from distributed_oracle_search_tpu.parallel import DistributionController
g = Graph.from_xy(xy)
dc = DistributionController("mod", 4, 4, g.n)
serve = _StreamedServe(g, dc, index, chunk=64)
q = np.load(qfile)
cost, plen, fin = serve.query(q)
assert bool(np.asarray(fin).all())
print(json.dumps({"pid": pid,
                  "bytes": serve.st.last_stats["bytes_streamed"],
                  "cost_sum": int(np.asarray(cost).sum())}))
"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["DOS_STREAM_ROW_CHUNK"] = "64"
    env["DOS_STREAM_RANGE_DENSITY"] = "0.0"
    here = os.path.dirname(os.path.abspath(__file__))
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, xy, index, qfile, coord, str(pid)],
        cwd=here, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        # kill BOTH controllers: the sibling is blocked in an allgather
        # waiting for its dead peer and would orphan otherwise
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        log("sharded stream: controller subprocess timed out")
        return None
    for pid, (p, o) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            log(f"sharded stream: process {pid} rc={p.returncode}: "
                f"{o[-500:]}")
            return None
    try:
        rows = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    except (json.JSONDecodeError, IndexError):
        log("sharded stream: unparseable output: "
            + " | ".join(o[-200:] for o in outs))
        return None
    if rows[0]["cost_sum"] != rows[1]["cost_sum"]:
        log(f"sharded stream: merged answers DISAGREE: {rows}")
        return None
    return [r["bytes"] for r in sorted(rows, key=lambda r: r["pid"])]


def main() -> None:
    import jax
    import numpy as np

    from distributed_oracle_search_tpu.utils.compile_cache import (
        use_compile_cache,
    )

    log(f"compile cache: {use_compile_cache()}")

    from distributed_oracle_search_tpu.data import (
        synth_city_graph, synth_scenario, synth_diff, write_xy,
    )
    from distributed_oracle_search_tpu.models.cpd import CPDOracle
    from distributed_oracle_search_tpu.parallel import DistributionController
    from distributed_oracle_search_tpu.parallel.mesh import make_mesh
    from distributed_oracle_search_tpu.utils import Timer

    width = int(os.environ.get("BENCH_WIDTH", 96))
    height = int(os.environ.get("BENCH_HEIGHT", 96))
    n_queries = int(os.environ.get("BENCH_QUERIES", 50_000))
    chunk = int(os.environ.get("BENCH_CHUNK", 512))

    devices = jax.devices()
    log(f"devices: {devices}")
    n_workers = len(devices)

    with Timer() as t_gen:
        g = synth_city_graph(width, height, seed=0)
        queries = synth_scenario(g.n, n_queries, seed=1)
    log(f"graph n={g.n} m={g.m} K={g.max_out_degree}; "
        f"{n_queries} queries; gen {t_gen}")

    dc = DistributionController("tpu", None, n_workers, g.n)
    mesh = make_mesh(n_workers=n_workers)

    # warm-up build: compiles the relaxation program (the persistent
    # compile cache usually absorbs this, but a cache miss would smear
    # ~40s of XLA compile into the timed build)
    with Timer() as t_bwarm:
        CPDOracle(g, dc, mesh=mesh).build(chunk=chunk, store_dists=True)
    log(f"build warm-up (compile): {t_bwarm}")

    def _main_build():
        o = CPDOracle(g, dc, mesh=mesh)
        o.build(chunk=chunk, store_dists=True)
        jax.block_until_ready(o.fm)
        return o
    # band: r03/r04 records measured ~1.1-1.3 s at the default 96x96;
    # non-default sizes get no band (bands are absolute seconds).
    # drop_prev: a second live oracle (fm + dists) would double peak HBM
    oracle, t_build_s = robust_time(
        _main_build, band_s=3.0 if (width, height) == (96, 96) else None,
        label="build", drop_prev=True)
    rows_per_s = g.n / t_build_s
    log(f"CPD build: {t_build_s:.2f}s ({rows_per_s:,.0f} target rows/s, "
        f"{g.n * g.n / t_build_s / 1e9:.2f} G entries/s)")

    # ---- post-build integrity gate: persist the freshly built index and
    # run the make_cpds --verify engine over it — digest/shape-check of
    # every block against the v2 manifest. A bench run that publishes
    # numbers off a torn/rotted index is worse than a failed run.
    # BENCH_VERIFY=0 skips.
    verify_stats = {}
    if os.environ.get("BENCH_VERIFY", "1") != "0":
        from distributed_oracle_search_tpu.models.cpd import (
            verify_exit_code, verify_index,
        )

        vdir = tempfile.mkdtemp(prefix="dos-verify-")
        try:
            with Timer() as t_save:
                oracle.save(vdir)
            with Timer() as t_verify:
                vreport = verify_index(vdir, dc=dc)
            assert verify_exit_code(vreport) == 0, (
                f"post-build integrity gate failed: {vreport}")
            verify_stats = {
                "verify_seconds": round(t_verify.interval, 3),
                "verify_blocks": int(vreport["total"]),
            }
            log(f"post-build verify: {vreport['total']} block(s) clean "
                f"in {t_verify.interval:.2f}s (save {t_save.interval:.2f}s)")
        finally:
            shutil.rmtree(vdir, ignore_errors=True)

    # congestion diff for the perturbed round (reference: one round/diff)
    dsrc, ddst, dw = synth_diff(g, frac=0.1, seed=2)
    w_diff = g.weights_with_diff((dsrc, ddst, dw))

    bench_table = os.environ.get("BENCH_TABLE", "1") != "0"

    # warm-up at the full scenario shape: compiles each query program once,
    # like the reference's resident fifo_auto loading before the campaign.
    # Timed PER PROGRAM so compile regressions are attributable; the table
    # section warms itself up later — its large prepare program used to
    # run here and skewed both this number and the walk timings after it
    warmups = {}
    with Timer() as t_compile:
        with Timer() as tw:
            oracle.query(queries)
        warmups["walk"] = round(tw.interval, 2)
        with Timer() as tw:
            oracle.query(queries, w_query=w_diff)
        warmups["walk_diff"] = round(tw.interval, 2)
        with Timer() as tw:
            oracle.query_dist(queries)
        warmups["dist"] = round(tw.interval, 2)
    log(f"query warm-up (compile): {t_compile} "
        + " ".join(f"{k}={v}s" for k, v in warmups.items()))

    def best_of(fn, reps: int = 3):
        """Best-of-N timing: the minimum is the reproducible figure."""
        out = None
        best = None
        for _ in range(reps):
            with Timer() as tt:
                out = fn()
            if best is None or tt.interval < best.interval:
                best = tt
        return out, best

    (cost, plen, finished), t_scen = best_of(lambda: oracle.query(queries))
    n_fin = int(finished.sum())
    qps = n_queries / t_scen.interval
    mean_plen = float(plen.mean())
    log(f"walk free-flow: {n_queries} in {t_scen} -> {qps:,.0f} q/s; "
        f"finished {n_fin}/{n_queries}, mean plen {mean_plen:.1f}")
    assert n_fin == n_queries, "benchmark correctness gate failed"

    (cost_d, plen_d, fin_d), t_diff = best_of(
        lambda: oracle.query(queries, w_query=w_diff))
    assert int(fin_d.sum()) == n_queries
    assert (cost_d >= cost).all(), "diffed costs must dominate free flow"
    log(f"walk diffed:   {n_queries} in {t_diff} -> "
        f"{n_queries / t_diff.interval:,.0f} q/s")

    (cost_g, fin_g), t_dist = best_of(lambda: oracle.query_dist(queries))
    assert (cost_g == cost).all(), "dist fast path must match the walk"
    log(f"dist gather:   {n_queries} in {t_dist} -> "
        f"{n_queries / t_dist.interval:,.0f} q/s")

    # ---- roofline: the walk does 2 scalar gathers per step per query
    # (fm slot + the packed (next-node, weight) pair); compare achieved
    # rate to a calibrated dependent-gather micro-kernel of the same
    # shape
    from distributed_oracle_search_tpu.ops.table_search import pick_buckets

    peak_gather = _calibrate_gather(g.n, n_queries)
    hbm_bw = _calibrate_hbm()
    # device-kernel time WITHOUT the host round trips: the end-to-end
    # walk pays a device->host fetch plus the query pack's upload,
    # which is transport, not kernel —
    # utilization is a kernel property, so the pack is pre-uploaded and
    # only the dispatched program is timed
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_oracle_search_tpu.parallel.mesh import (
        DATA_AXIS, WORKER_AXIS,
    )
    from distributed_oracle_search_tpu.parallel.sharded import _query_fn
    ra, sa, ta, va, _ = oracle.route(queries)
    qsh = NamedSharding(oracle.mesh, P(DATA_AXIS, WORKER_AXIS, None))
    ra_d, sa_d, ta_d, va_d = jax.device_put((ra, sa, ta, va), qsh)
    kern_fn = _query_fn(oracle.mesh, 0, -1)
    # stall-guarded like every timed section: r04's 0.169 s reading (vs
    # 0.113 s re-measured in a healthy window) dragged the utilization
    # figure to 0.457 — a window artifact, not a kernel property
    _, t_kern_s = robust_time(
        lambda: jax.block_until_ready(kern_fn(
            oracle.dg, oracle.fm, ra_d, sa_d, ta_d, va_d,
            oracle.dg.w_pad)),
        reps=3, band_s=0.13 if (width, height) == (96, 96) else None,
        label="walk-kernel")
    # the bucketed walk (ops.table_search n_buckets) runs each bucket to
    # its OWN max length: reconstruct issued gathers from route()'s
    # actual per-device layout (each (data, worker) plane is an
    # est-sorted, separately padded [qmax] column). Utilization compares
    # the CRITICAL-PATH device (max lanes) to the single-device peak.
    _, _, _, valid_dwq, (act, sd, sw, sq) = oracle.route(queries)
    dgrid, wgrid, qmax = valid_dwq.shape
    plen_dwq = np.zeros((dgrid, wgrid, qmax))
    plen_dwq[sd[act], sw[act], sq[act]] = np.asarray(plen)[act]
    b = pick_buckets(qmax, 0)
    qb = qmax // b
    unroll = 8
    per_bucket_max = plen_dwq.reshape(dgrid, wgrid, b, qb).max(axis=3)
    lanes_dev = (np.ceil(per_bucket_max / unroll) * unroll).sum(
        axis=2) * qb                                  # [D, W] per device
    lanes_issued = float(lanes_dev.max())
    gathers_per_step = 2          # fm slot + packed (next, weight) pair
    achieved_gather = ((n_queries / (dgrid * wgrid)) * mean_plen
                       * gathers_per_step / t_kern_s)
    issued_gather = lanes_issued * gathers_per_step / t_kern_s
    # honest lane accounting: walk_gather_utilization rewards padded
    # lanes (wider buckets inflate the issued rate while slowing the
    # answer — the table_search.py knob comment). useful_lane_fraction
    # is the unskewed figure for kernel-vs-kernel comparisons: real
    # moves of non-pad queries over ALL issued lane-steps, fleet-wide
    lanes_issued_total = float(lanes_dev.sum())
    useful_lane_fraction = (float(plen_dwq.sum()) / lanes_issued_total
                            if lanes_issued_total else 0.0)
    log(f"roofline: kernel {t_kern_s:.3f}s, peak gather "
        f"{peak_gather / 1e6:,.0f} M elem/s, "
        f"useful {achieved_gather / 1e6:,.0f} "
        f"({achieved_gather / peak_gather:.0%}), issued "
        f"{issued_gather / 1e6:,.0f} ({issued_gather / peak_gather:.0%}), "
        f"issue efficiency {achieved_gather / issued_gather:.0%}; "
        f"HBM {hbm_bw / 1e9:,.0f} GB/s")
    # XLA's own accounting of the SAME program (obs.device): FLOPs /
    # bytes-accessed / HBM footprint per compiled program, plus the
    # derived achieved-vs-peak gather-bandwidth point — the before/after
    # baseline ROADMAP item 1 (Pallas walk kernel) is judged against
    from distributed_oracle_search_tpu.obs import device as obs_device
    walk_costs = obs_device.analyze(
        kern_fn, oracle.dg, oracle.fm, ra_d, sa_d, ta_d, va_d,
        oracle.dg.w_pad)
    walk_costs = obs_device.derive_bandwidth(
        walk_costs, t_kern_s, hbm_bw / 1e9)
    if walk_costs:
        if "achieved_gbps" in walk_costs:
            log(f"roofline (XLA): {walk_costs.get('flops', 0):,.0f} "
                f"FLOPs, {walk_costs['bytes_accessed'] / 1e6:,.1f} MB "
                f"accessed -> {walk_costs['achieved_gbps']:,.1f} GB/s "
                f"achieved ({walk_costs['hbm_bw_utilization']:.0%} of "
                f"the streamed-HBM peak)")
        obs_device.record("walk-kernel", walk_costs)

    # ---- measured CPU denominator: the SAME graph + scenario through the
    # native OpenMP engine (full build + resident fifo_auto campaign over
    # the real FIFO wire). This is the reference pipeline's stand-in; the
    # north-star "≥10x build" (BASELINE.md) is judged against it.
    # BENCH_CPU=0 skips.
    cpu_stats = {}
    if os.environ.get("BENCH_CPU", "1") != "0":
        bins = _native_bins()
        if bins is None:
            log("CPU baseline skipped: no native toolchain")
        else:
            cdir = tempfile.mkdtemp(prefix="dos-cpu-")
            try:
                xy = os.path.join(cdir, "city.xy")
                cidx = os.path.join(cdir, "index")
                write_xy(xy, g.xs, g.ys, g.src, g.dst, g.w)
                t_cpu_b_s = _timed_cpu_build(
                    bins, ["--input", xy, "--partmethod", "mod",
                           "--partkey", "1", "--workerid", "0",
                           "--maxworker", "1", "--outdir", cidx],
                    label="cpu-build")
                t_cpu_q = _cpu_query_campaign(bins, xy, cidx, queries,
                                              cdir)
                cores = os.cpu_count() or 1
                cpu_qps = n_queries / t_cpu_q
                build_speedup = t_cpu_b_s / t_build_s
                query_speedup = t_cpu_q / t_scen.interval
                log(f"CPU baseline ({cores} core(s)): build "
                    f"{t_cpu_b_s:.2f}s "
                    f"(tpu {build_speedup:.1f}x), campaign t_search "
                    f"{t_cpu_q:.3f}s -> {cpu_qps:,.0f} q/s "
                    f"(tpu walk {query_speedup:.2f}x, dist "
                    f"{t_cpu_q / t_dist.interval:.2f}x)")
                cpu_stats = {
                    "cpu_cores": cores,
                    # every speedup below divides by a campaign run on
                    # cpu_cores core(s). Under the reference's all-cores
                    # OpenMP deployment (README.md:95) and linear
                    # scaling, a C-core host is matched when C equals
                    # the *_parity_cores figure — the form in which the
                    # north-star "≥10x vs OpenMP all threads"
                    # (BASELINE.md) is checkable off this host.
                    "cpu_denominator": (
                        f"measured on {cores} core(s); parity_cores = "
                        "OpenMP cores (linear scaling) needed to match"),
                    "cpu_build_seconds": round(t_cpu_b_s, 2),
                    "cpu_queries_per_sec": round(cpu_qps, 1),
                    "tpu_build_speedup": round(build_speedup, 2),
                    "tpu_build_parity_cores": round(
                        build_speedup * cores, 2),
                    "tpu_query_speedup": round(query_speedup, 3),
                    "tpu_dist_speedup": round(
                        t_cpu_q / t_dist.interval, 3),
                }

                # bulk-dist round: the distance fast path is ONE gather
                # per query, so at 50k queries its time is mostly fixed
                # dispatch+transfer. A 500k-query
                # round amortizes the fixed cost; the CPU denominator
                # is MEASURED on the same 500k (not extrapolated).
                bq = int(os.environ.get("BENCH_DIST_BULK", 500_000))
                q_bulk = synth_scenario(g.n, bq, seed=11)
                oracle.query_dist(q_bulk)        # warm-up: compile
                (cb_b, fb_b), t_bulk = best_of(
                    lambda: oracle.query_dist(q_bulk))
                assert bool(np.asarray(fb_b).all())
                t_cpu_bulk = _cpu_query_campaign(bins, xy, cidx, q_bulk,
                                                 cdir)
                log(f"dist bulk: {bq} in {t_bulk} -> "
                    f"{bq / t_bulk.interval:,.0f} q/s; CPU campaign "
                    f"{t_cpu_bulk:.3f}s (tpu dist "
                    f"{t_cpu_bulk / t_bulk.interval:.2f}x)")
                cpu_stats.update({
                    "dist_bulk_queries": bq,
                    "dist_bulk_queries_per_sec": round(
                        bq / t_bulk.interval, 1),
                    "cpu_bulk_queries_per_sec": round(bq / t_cpu_bulk, 1),
                    "tpu_dist_bulk_speedup": round(
                        t_cpu_bulk / t_bulk.interval, 3),
                })

                # native algorithm families (README: backends are
                # "interchangeable per algorithm family") — measured
                # campaign rates for astar and ch next to the batched
                # device A*'s rate, all on the same query subset (A* is
                # ~three orders slower per query than a table lookup;
                # the subset keeps the bench's runtime bounded)
                # 1024 keeps the device A*'s ~27 q/s measurement out of
                # the bench's critical path (~2.5 min at 2048)
                aq = min(int(os.environ.get("BENCH_ASTAR_QUERIES", 1024)),
                         n_queries)
                q_sub = np.asarray(queries[:aq])
                t_cpu_as = _cpu_query_campaign(bins, xy, cidx, q_sub,
                                               cdir, alg="astar")
                t_cpu_ch = _cpu_query_campaign(bins, xy, cidx, q_sub,
                                               cdir, alg="ch")
                from distributed_oracle_search_tpu.ops.batched_astar \
                    import astar_batch_np
                astar_ctx: dict = {}
                astar_batch_np(g, q_sub, ctx=astar_ctx,
                               w_key="free")     # warm-up: compile
                (ca, pa, fa, _cnt), t_dev_as = best_of(
                    lambda: astar_batch_np(g, q_sub, ctx=astar_ctx,
                                           w_key="free"), reps=2)
                assert bool(fa.all())
                assert (ca == np.asarray(cost)[:aq]).all(), \
                    "device A* must match the walk's shortest costs"
                log(f"alg families ({aq} queries): CPU astar "
                    f"{aq / t_cpu_as:,.0f} q/s, CPU ch "
                    f"{aq / t_cpu_ch:,.0f} q/s, device astar "
                    f"{aq / t_dev_as.interval:,.0f} q/s")
                cpu_stats.update({
                    "alg_family_queries": aq,
                    "cpu_astar_queries_per_sec": round(aq / t_cpu_as, 1),
                    "cpu_ch_queries_per_sec": round(aq / t_cpu_ch, 1),
                    "tpu_astar_queries_per_sec": round(
                        aq / t_dev_as.interval, 1),
                })
            finally:
                shutil.rmtree(cdir, ignore_errors=True)

    # pointer-doubling amortization path: whole-shard cost tables for the
    # DIFFED weights, then gather-speed answers. Costs O(R*N*log L)
    # gathers up front — the >1M-query trade (BASELINE.md configs[4]).
    # BENCH_TABLE=0 skips it for quick runs.
    table_stats = {}
    if bench_table:
        # warm-up: compile the prepare/lookup programs at shape on the
        # free-flow weights, so the timed run below is steady-state (and
        # the compile cost is attributable here, not smeared into it)
        with Timer() as t_tabc:
            warm = oracle.prepare_weights(None)
            # full scenario shape: a different batch size would compile a
            # different lookup program and the timed run would pay it
            oracle.query_table(warm, queries)
            jax.block_until_ready(warm[0])
            del warm
        log(f"table warm-up (compile): {t_tabc}")
        # table prepares run under the same stall guard as every build;
        # drop_prev: two live table sets would double peak device memory
        # past what the budget gate admitted
        tables, t_prep_s = robust_time(
            lambda: jax.block_until_ready(oracle.prepare_weights(w_diff)),
            drop_prev=True, label="table-prepare")
        (cost_t, plen_t, fin_t), t_tab = best_of(
            lambda: oracle.query_table(tables, queries))
        assert (cost_t == cost_d).all(), \
            "table path must match the diff walk"
        assert (plen_t == plen_d).all() and (fin_t == fin_d).all()
        # break-even from THIS run's captured rates (the pointer-doubling
        # cost model quotes this number; r03's README derived it from
        # optimistic rates — the bench is now the single source):
        # prepare pays off once saved per-query time covers it
        walk_qps_diff = n_queries / t_diff.interval
        tab_qps = n_queries / t_tab.interval
        per_q_saved = 1.0 / walk_qps_diff - 1.0 / tab_qps
        breakeven = (int(t_prep_s / per_q_saved)
                     if per_q_saved > 0 else -1)
        be_txt = (f"break-even {breakeven:,} queries" if breakeven >= 0
                  else "break-even n/a (lookups no faster than the walk)")
        log(f"diff tables:   prepare {t_prep_s:.2f}s; {n_queries} in {t_tab} -> "
            f"{tab_qps:,.0f} q/s; {be_txt}")
        table_stats = {
            "table_prepare_seconds": round(t_prep_s, 3),
            "table_queries_per_sec": round(tab_qps, 1),
            "table_breakeven_queries": breakeven,
        }
        del tables

        # fused multi-diff tables: the doubling recursion is shared
        # across diffs, so D diffs' tables cost ~one prepare's gather
        # traffic (only the packed payload widens). The sequential
        # comparison is D x this run's measured single prepare — same
        # program, same shapes, so the product is exact, not a model.
        n_tab_diffs = 4
        w4t = [w_diff] + [
            g.weights_with_diff(synth_diff(g, frac=0.1, seed=80 + i))
            for i in range(n_tab_diffs - 1)]
        with Timer() as t_tm_c:          # compile (fresh program)
            warm4 = oracle.prepare_weights_multi(w4t)
            oracle.query_table_multi(warm4, queries)
            jax.block_until_ready(warm4[0])
            del warm4
        log(f"multi-table warm-up (compile): {t_tm_c}")
        tables4, t_prep4_s = robust_time(
            lambda: jax.block_until_ready(
                oracle.prepare_weights_multi(w4t)),
            drop_prev=True, label="table-prepare-multi")
        (cm4t, pm4t, fm4t), t_tab4 = best_of(
            lambda: oracle.query_table_multi(tables4, queries))
        assert (cm4t[0] == cost_t).all(), \
            "fused table plane 0 must match the single-diff tables"
        amort = n_tab_diffs * t_prep_s / t_prep4_s
        log(f"fused tables: {n_tab_diffs} diffs prepared in "
            f"{t_prep4_s:.2f}s "
            f"(vs {n_tab_diffs} x {t_prep_s:.1f}s sequential = "
            f"{amort:.2f}x amortization); lookups "
            f"{n_queries / t_tab4.interval:,.0f} q/s x {n_tab_diffs} "
            f"diffs/gather")
        table_stats.update({
            "table_multi_diffs": n_tab_diffs,
            "table_multi_prepare_seconds": round(t_prep4_s, 3),
            "table_multi_amortization": round(amort, 3),
            "table_multi_queries_per_sec": round(
                n_queries / t_tab4.interval, 1),
        })
        del tables4

    # ---- scale section: 102k-node city, single chip. One complete worker
    # shard (div/8) built with the fast-sweeping kernel and served
    # STREAMED from the on-disk block files — the serving mode for indexes
    # that exceed HBM (full fm at this scale: N^2 = 10.5 GB single-shard).
    scale_stats = {}
    if os.environ.get("BENCH_SCALE", "1") != "0":
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.models.streamed import (
            StreamedCPDOracle,
        )

        side = int(os.environ.get("BENCH_SCALE_SIDE", 320))
        sq = int(os.environ.get("BENCH_SCALE_QUERIES", 20_000))
        g2 = synth_city_graph(side, side, seed=0)
        w_scale = 8
        per_w = -(-g2.n // w_scale)
        dc2 = DistributionController("div", per_w, w_scale, g2.n)
        outdir = tempfile.mkdtemp(prefix="dos-scale-")
        try:
            log(f"scale: n={g2.n} building worker 0 shard "
                f"({dc2.n_owned(0)} rows, sweep kernel)...")
            # warm-up: compile the sweep program at the build chunk shape
            # (persistent-cached across runs) so the timed build is
            # steady-state like every other section
            from distributed_oracle_search_tpu.models.cpd import (
                pick_build_kernel,
            )
            from distributed_oracle_search_tpu.ops import DeviceGraph
            from distributed_oracle_search_tpu.ops.grid_sweep import (
                build_fm_columns_sweep,
            )
            _, gg2 = pick_build_kernel(g2, "sweep")
            dg2 = DeviceGraph.from_graph(g2)
            sc_chunk = int(os.environ.get("BENCH_SCALE_CHUNK", 1024))
            jax.block_until_ready(build_fm_columns_sweep(
                dg2, gg2, np.arange(sc_chunk, dtype=np.int32)))
            # chunk=1024: the sweep kernel's while-body holds several
            # skewed [CA, H, B] buffers; 1024 rows (~5 GB working set at
            # this graph size) measured 20% faster per row than 512 and
            # fits a 16 GB chip with the pipelined double-block drain

            def _reset_scale():         # builds resume off block files
                # the ledger goes too: it grows a journal line per
                # block per rep, and leaving it would hand the next
                # rep a fatter journal even with resume off
                for f in os.listdir(outdir):
                    if f.startswith(("cpd-", "build-")):
                        os.unlink(os.path.join(outdir, f))
            # band: candidate r04 measured 43 s (297 rows/s); the record
            # capture's 116 s was a documented >2.5x stall — 70 s flags
            # it. Absolute-seconds bands only apply at the default knobs.
            scale_default = side == 320 and sc_chunk == 1024
            # resume=False hoists the per-block ledger re-read out of
            # the timed region: scale_build_rows_per_sec measures
            # compute + block writes, not journal parsing (the reset
            # already guarantees every block is missing)
            _, t_b2_s = robust_time(
                lambda: build_worker_shard(g2, dc2, 0, outdir,
                                           chunk=sc_chunk, method="sweep",
                                           resume=False),
                reset=_reset_scale,
                band_s=70.0 if scale_default else None,
                label="scale-build")
            rows0 = dc2.n_owned(0)
            rps2 = rows0 / t_b2_s
            full_est = g2.n / rps2
            write_index_manifest(outdir, dc2, workers=[0])
            log(f"scale build: {rows0} rows in {t_b2_s:.2f}s -> "
                f"{rps2:,.0f} rows/s ({rps2 * g2.n / 1e9:.2f} G "
                f"entries/s), full-index extrapolation {full_est:,.0f}s")

            rng = np.random.default_rng(3)
            q2 = np.stack([rng.integers(0, g2.n, sq),
                           rng.integers(0, rows0, sq)], axis=1)
            # explicit cache budget: this section's 1.7 GB chunk
            # working set must stay resident whatever the device
            st = StreamedCPDOracle(g2, dc2, outdir, row_chunk=4096,
                                   cache_bytes=4 << 30)
            st.query(q2[:256])                 # warm-up: compile
            # prime the persisted RLE sidecars UNTIMED (the first-ever
            # round pays the one-time encode, like the compile warm-up
            # pays XLA): every timed rep below then runs the same
            # deployment-steady-state cold path — device caches empty,
            # compressed index on disk — so best-of reps are symmetric
            st.clear_cache()
            st.query(q2)
            # cold round: every rep drops the LRU first so each pays
            # the full (compressed) upload; wire bytes are
            # deterministic across reps, so the stats read after the
            # loop describe the best run too. Band: ~3 s measured for
            # the sidecar-backed path; 15 s flags a stall

            def _cold():
                st.clear_cache()
                return st.query(q2)
            (c2, p2, f2), t_q2_s = robust_time(
                _cold,
                band_s=15.0 if scale_default and sq == 20_000 else None,
                label="scale-cold-stream")
            assert bool(f2.all()), "scale campaign left unfinished queries"
            cold_qps = sq / t_q2_s
            # snapshot BEFORE the warm rounds below overwrite last_stats
            # with zero-upload rounds (the road section does the same)
            scale_cold_stats = dict(st.last_stats)
            cold_mb = st.last_stats["bytes_streamed"] / 1e6
            # captured HERE: the warm best_of rounds below overwrite
            # last_stats with zero-byte rounds
            cold_raw_mb = st.last_stats["bytes_raw"] / 1e6
            # packing that RAN, not merely the enabled flag (chunks
            # fall back individually when too many entries escape)
            cold_pack4 = st.last_stats["chunks_packed"] > 0
            mbps = st.last_stats["bytes_streamed"] / t_q2_s / 1e6
            log(f"scale streamed (cold): {sq} queries in {t_q2_s:.2f}s -> "
                f"{cold_qps:,.0f} q/s; streamed {cold_mb:,.0f} MB wire"
                f" ({cold_raw_mb:,.0f} MB raw fm"
                f"{', 4-bit packed' if cold_pack4 else ''};"
                f" {mbps:,.0f} MB/s incl. walk)")
            # round 2+ — the serving steady state (a resident streaming
            # server answers MANY rounds over overlapping targets, one
            # per diff, reference process_query.py:178): the device LRU
            # holds every chunk, so no bytes move
            (c2w, p2w, f2w), t_q2w = best_of(lambda: st.query(q2))
            assert st.last_stats["bytes_streamed"] == 0, \
                "warm round must be fully cache-resident"
            assert (c2w == c2).all() and (p2w == p2).all()
            warm_qps = sq / t_q2w.interval
            log(f"scale streamed (warm, chunks cached): {sq} in {t_q2w} "
                f"-> {warm_qps:,.0f} q/s; 0 MB streamed")
            scale_stats = {
                "scale_nodes": g2.n,
                "scale_build_rows": rows0,
                "scale_build_seconds": round(t_b2_s, 2),
                "scale_build_rows_per_sec": round(rps2, 1),
                "scale_full_build_est_seconds": round(full_est, 1),
                # cold keeps the r03 key (rounds stay comparable across
                # bench artifacts); the cache-warm steady state is its
                # own key, never a silent redefinition. scale_stream_mb
                # stays the RAW fm bytes the cold round served (the r03
                # unit); the wire bytes and packing state get their own
                # keys so the 4-bit-packed upload is visible, not a
                # silent 2x accounting change
                "scale_stream_queries_per_sec": round(cold_qps, 1),
                "scale_stream_mb": round(cold_raw_mb, 1),
                "scale_stream_wire_mb": round(cold_mb, 1),
                "scale_stream_pack4": cold_pack4,
                # which wire path the cold round of record actually ran
                # (RLE chunks / persisted-sidecar hits out of row_chunks)
                "scale_stream_rle_chunks":
                    scale_cold_stats["chunks_rle"],
                "scale_stream_sidecar_hits":
                    scale_cold_stats["sidecar_hits"],
                "scale_stream_warm_queries_per_sec": round(warm_qps, 1),
                "scale_stream_warm_mb": 0.0,
            }

            # resident serving of the SAME shard: 1.3 GB int8 fits HBM —
            # this is one chip of the real multi-chip deployment (each
            # chip holds its worker's shard resident; streaming is for
            # the regime where even one shard exceeds HBM)
            import jax.numpy as jnp

            from distributed_oracle_search_tpu.ops.table_search import (
                table_search_batch,
            )

            blocks = sorted(f for f in os.listdir(outdir)
                            if f.startswith("cpd-w00000"))
            fm0 = jnp.asarray(np.concatenate(
                [np.load(os.path.join(outdir, f)) for f in blocks]))
            # div partition: worker 0's owned row index == target node id
            est2 = (np.abs(g2.xs[q2[:, 0]] - g2.xs[q2[:, 1]])
                    + np.abs(g2.ys[q2[:, 0]] - g2.ys[q2[:, 1]]))
            order2 = np.argsort(est2, kind="stable")
            qpad = 1 << (sq - 1).bit_length()
            rr = np.zeros(qpad, np.int32)
            ss = np.zeros(qpad, np.int32)
            tt2 = np.zeros(qpad, np.int32)
            vv = np.zeros(qpad, bool)
            rr[:sq] = q2[order2, 1]
            ss[:sq] = q2[order2, 0]
            tt2[:sq] = q2[order2, 1]
            vv[:sq] = True

            def resident():
                return jax.block_until_ready(table_search_batch(
                    dg2, fm0, rr, ss, tt2, dg2.w_pad, valid=vv))
            (cr, pr, fr), t_res = best_of(resident)
            assert bool(np.asarray(fr)[:sq].all())
            assert (np.asarray(cr)[np.argsort(order2)] == c2).all(), \
                "resident shard serve must match streamed answers"
            rqps = sq / t_res.interval
            log(f"scale resident: {sq} queries in {t_res} -> "
                f"{rqps:,.0f} q/s (worker-0 shard, "
                f"{fm0.nbytes / 1e9:.1f} GB on HBM)")
            scale_stats["scale_resident_queries_per_sec"] = round(rqps, 1)
            del fm0

            # CPU at the same scale (BENCH_CPU=0 skips): build rate from
            # a 512-row sub-worker (div/512 — a full worker shard would
            # take minutes), serve from the SAME on-disk index the sweep
            # kernel just wrote (block files are builder-agnostic,
            # tests/test_native.py block parity)
            if os.environ.get("BENCH_CPU", "1") != "0":
                bins = _native_bins()
                if bins is not None:
                    xy2 = os.path.join(outdir, "scale.xy")
                    write_xy(xy2, g2.xs, g2.ys, g2.src, g2.dst, g2.w)
                    sub_rows = 512
                    t_cb2_s = _timed_cpu_build(
                        bins, ["--input", xy2, "--partmethod", "div",
                               "--partkey", str(sub_rows),
                               "--workerid", "0", "--maxworker",
                               str(-(-g2.n // sub_rows)), "--outdir",
                               os.path.join(outdir, "cpuidx")],
                        label="scale-cpu-build")
                    cpu_rps2 = sub_rows / t_cb2_s
                    t_cpu_q2 = _cpu_query_campaign(
                        bins, xy2, outdir, q2, outdir,
                        partmethod="div", partkey=per_w, workerid=0,
                        maxworker=w_scale)
                    cpu_qps2 = sq / t_cpu_q2
                    log(f"scale CPU: build {cpu_rps2:,.0f} rows/s "
                        f"(tpu {rps2 / cpu_rps2:.1f}x), campaign "
                        f"t_search {t_cpu_q2:.3f}s -> {cpu_qps2:,.0f} "
                        f"q/s (tpu streamed {t_cpu_q2 / t_q2_s:.2f}"
                        f"x)")
                    cores = os.cpu_count() or 1
                    scale_stats.update({
                        "scale_cpu_build_rows_per_sec": round(cpu_rps2, 1),
                        "scale_cpu_queries_per_sec": round(cpu_qps2, 1),
                        "scale_tpu_build_speedup": round(
                            rps2 / cpu_rps2, 2),
                        "scale_build_parity_cores": round(
                            rps2 / cpu_rps2 * cores, 2),
                        "scale_tpu_stream_speedup": round(
                            t_cpu_q2 / t_q2_s, 3),
                        "scale_tpu_stream_warm_speedup": round(
                            t_cpu_q2 / t_q2w.interval, 3),
                        "scale_tpu_resident_speedup": round(
                            t_cpu_q2 / t_res.interval, 3),
                    })
        finally:
            shutil.rmtree(outdir, ignore_errors=True)

    # ---- road section: non-grid, degree-skewed 264k-node network (the
    # DIMACS stand-in, BASELINE.md configs[5]) — the regime where the
    # grid/shift build gates MUST fall back gracefully. Build via the ELL
    # fallback on TPU vs per-source Dijkstra on CPU; serve streamed and
    # resident from the same index. BENCH_ROAD=0 skips.
    road_stats = {}
    comp_stats = {}
    if os.environ.get("BENCH_ROAD", "1") != "0":
        import jax.numpy as jnp

        from distributed_oracle_search_tpu.data import synth_road_network
        from distributed_oracle_search_tpu.models.cpd import (
            pick_build_kernel, write_index_manifest,
        )
        from distributed_oracle_search_tpu.models.streamed import (
            StreamedCPDOracle,
        )
        from distributed_oracle_search_tpu.ops import DeviceGraph
        from distributed_oracle_search_tpu.ops.shift_relax import (
            split_coverage,
        )
        from distributed_oracle_search_tpu.ops.table_search import (
            table_search_batch,
        )

        rn = int(os.environ.get("BENCH_ROAD_NODES", 264_000))
        g3 = synth_road_network(rn, seed=0)
        _, ws_raw, _, wl_raw = g3.shift_split()
        cov_raw = split_coverage(ws_raw, wl_raw)
        with Timer() as t_rcm:
            g3 = g3.reorder(g3.rcm_order())
        _, ws_rcm, _, wl_rcm = g3.shift_split()
        cov_rcm = split_coverage(ws_rcm, wl_rcm)
        kind3, st3k = pick_build_kernel(g3, "auto")
        log(f"road: n={g3.n} m={g3.m} K={g3.max_out_degree}; rcm reorder "
            f"{t_rcm}; shift coverage {cov_raw:.1%} -> {cov_rcm:.1%}; "
            f"auto build kernel = {kind3} (grid/shift gates fell back "
            f"as designed)")

        sub = 512                       # rows per serving sub-worker
        mw3 = -(-g3.n // sub)
        dc3 = DistributionController("div", sub, mw3, g3.n)
        out3 = tempfile.mkdtemp(prefix="dos-road-")
        try:
            # TPU build via the auto-picked kernel (delta-stepping
            # frontier queue on the RCM-ordered road graph). 2048 timed
            # rows: the frontier's per-iteration cost amortizes over
            # the batch (measured ~10% more rows/s than 512-row calls)
            # and the fixed fetch/dispatch costs quarter; rows/s stays
            # directly comparable to the 512-row CPU build below (both
            # are per-row rates of linear-in-rows work)
            trows = int(os.environ.get("BENCH_ROAD_ROWS", 2048))
            dg3 = DeviceGraph.from_graph(g3)
            if kind3 == "frontier":
                from distributed_oracle_search_tpu.ops.frontier_relax \
                    import build_fm_columns_frontier
                build3 = lambda t: build_fm_columns_frontier(  # noqa: E731
                    dg3, st3k, t)
            elif kind3 == "ellsplit":
                from distributed_oracle_search_tpu.ops.ell_split import (
                    build_fm_columns_ellsplit,
                )
                build3 = lambda t: build_fm_columns_ellsplit(  # noqa: E731
                    dg3, st3k, t)
            elif kind3 == "shift":
                from distributed_oracle_search_tpu.ops.shift_relax import (
                    build_fm_columns_shift,
                )
                build3 = lambda t: build_fm_columns_shift(  # noqa: E731
                    dg3, st3k, t)
            elif kind3 == "sweep":
                from distributed_oracle_search_tpu.ops.grid_sweep import (
                    build_fm_columns_sweep,
                )
                build3 = lambda t: build_fm_columns_sweep(  # noqa: E731
                    dg3, st3k, t)
            else:
                from distributed_oracle_search_tpu.ops import (
                    build_fm_columns,
                )
                build3 = lambda t: build_fm_columns(  # noqa: E731
                    dg3, jnp.asarray(t))
            from distributed_oracle_search_tpu.models.cpd import fetch_fm
            tgt64 = np.arange(trows, dtype=np.int32)
            fetch_fm(build3(tgt64))           # compile build + encode
            # end-to-end incl. the host materialization (the build's
            # real product is block files): the RLE fetch ships ~3
            # bytes/run instead of the raw bytes.
            # Band: ~27 s for 2048 rows at the default 264k nodes,
            # scaled linearly for other BENCH_ROAD_ROWS settings
            fm64, t_b3_s = robust_time(
                lambda: fetch_fm(build3(tgt64)),             # [trows, N]
                band_s=(40.0 * trows / 2048 if rn == 264_000
                        else None),
                label="road-build")
            tpu_rps3 = trows / t_b3_s
            log(f"road TPU build ({kind3}): {trows} rows in "
                f"{t_b3_s:.2f}s -> {tpu_rps3:,.1f} rows/s")

            # ---- compressed residency (ROADMAP item 1): the SAME road
            # rows resident raw vs RLE/pack4-compressed with
            # decompress-at-use (models.resident, DOS_CPD_RESIDENT).
            # The ratio is a codec property of THIS shard's bytes; the
            # walk figures time the serving path's actual shape — the
            # batch's distinct target rows inflate on device, then the
            # same walk kernel runs — against the raw-resident walk on
            # identical queries. BENCH_COMPRESSED=0 skips.
            if os.environ.get("BENCH_COMPRESSED", "1") != "0":
                from distributed_oracle_search_tpu.models.resident \
                    import make_resident

                ctab, ccodec = make_resident(fm64, codec="auto")
                if ccodec == "raw":
                    log("compressed: auto codec degraded to raw "
                        "(incompressible shard); section skipped")
                else:
                    cratio = fm64.nbytes / ctab.nbytes
                    log(f"compressed: {ccodec} residency "
                        f"{fm64.nbytes / 2**20:.1f} MB -> "
                        f"{ctab.nbytes / 2**20:.1f} MB "
                        f"({cratio:.2f}x)")
                    rngc = np.random.default_rng(9)
                    cq = int(os.environ.get("BENCH_COMPRESSED_QUERIES",
                                            20_000))
                    qsc = rngc.integers(0, g3.n, cq)
                    qtc = rngc.integers(0, trows, cq)
                    estc = (np.abs(g3.xs[qsc] - g3.xs[qtc])
                            + np.abs(g3.ys[qsc] - g3.ys[qtc]))
                    oc = np.argsort(estc, kind="stable")
                    qpc = 1 << (cq - 1).bit_length()
                    rrc = np.zeros(qpc, np.int32)
                    ssc = np.zeros(qpc, np.int32)
                    ttc = np.zeros(qpc, np.int32)
                    vvc = np.zeros(qpc, bool)
                    rrc[:cq] = qtc[oc]
                    ssc[:cq] = qsc[oc]
                    ttc[:cq] = qtc[oc]
                    vvc[:cq] = True
                    fmcr = jnp.asarray(fm64)
                    (ccr, _pcr, _fcr), t_craw = best_of(
                        lambda: jax.block_until_ready(table_search_batch(
                            dg3, fmcr, rrc, ssc, ttc, dg3.w_pad,
                            valid=vvc)))
                    # the engine's decompress-at-use shape: distinct
                    # rows inflate once, row ids remap onto the dense
                    # block, the walk is unchanged
                    urc, rinvc = np.unique(rrc, return_inverse=True)
                    rpadc = 1 << (len(urc) - 1).bit_length()
                    ruc = np.zeros(rpadc, np.int32)
                    ruc[:len(urc)] = urc
                    ruc_d = jnp.asarray(ruc)
                    rrc2 = rinvc.reshape(-1).astype(np.int32)

                    def comp_walk():
                        fmw = ctab.decompress_rows(ruc_d)
                        return jax.block_until_ready(table_search_batch(
                            dg3, fmw, rrc2, ssc, ttc, dg3.w_pad,
                            valid=vvc))

                    (ccc, _pcc, _fcc), t_ccmp = best_of(comp_walk)
                    assert (np.asarray(ccc) == np.asarray(ccr)).all(), \
                        "compressed-resident walk != raw-resident walk"
                    _, t_cdec = best_of(
                        lambda: jax.block_until_ready(
                            ctab.decompress_rows(ruc_d)))
                    cqps_raw = cq / t_craw.interval
                    cqps_cmp = cq / t_ccmp.interval
                    log(f"compressed walk: raw {cqps_raw:,.0f} q/s vs "
                        f"{ccodec} {cqps_cmp:,.0f} q/s "
                        f"({cqps_cmp / cqps_raw:.2f}x; decompress "
                        f"{t_cdec.interval * 1e3:.1f} ms/batch for "
                        f"{len(urc)} distinct rows)")
                    comp_stats = {
                        "compressed_codec": ccodec,
                        "compressed_rows": trows,
                        "compressed_raw_mb": round(
                            fm64.nbytes / 2**20, 1),
                        "compressed_resident_mb": round(
                            ctab.nbytes / 2**20, 1),
                        "cpd_resident_bytes_ratio": round(cratio, 2),
                        "compressed_raw_walk_queries_per_sec": round(
                            cqps_raw, 1),
                        "compressed_walk_queries_per_sec": round(
                            cqps_cmp, 1),
                        "compressed_vs_raw_walk_ratio": round(
                            cqps_cmp / cqps_raw, 3),
                        "compressed_decompress_seconds": round(
                            t_cdec.interval, 4),
                    }
                    del fmcr, ctab

            bins = (_native_bins()
                    if os.environ.get("BENCH_CPU", "1") != "0" else None)
            if bins is not None:
                xy3 = os.path.join(out3, "road.xy")
                write_xy(xy3, g3.xs, g3.ys, g3.src, g3.dst, g3.w)
                t_cb3_s = _timed_cpu_build(
                    bins, ["--input", xy3, "--partmethod", "div",
                           "--partkey", str(sub), "--workerid", "0",
                           "--maxworker", str(mw3), "--outdir", out3],
                    label="road-cpu-build")
                cpu_rps3 = sub / t_cb3_s
                # correctness gate: ELL build and native Dijkstra must
                # produce bit-identical first moves on this graph too
                blk0 = np.load(os.path.join(
                    out3, "cpd-w00000-b00000.npy"))
                # the native sub-worker owns 512 rows; parity on the
                # overlap (the kernels' tie-breaks must agree row-wise)
                npar = min(trows, len(blk0))
                assert (blk0[:npar] == fm64[:npar]).all(), \
                    "road: TPU ELL fm rows != native Dijkstra rows"
                log(f"road CPU build: {sub} rows in {t_cb3_s:.2f}s -> "
                    f"{cpu_rps3:,.1f} rows/s (tpu "
                    f"{tpu_rps3 / cpu_rps3:.2f}x); fm parity ok")

                write_index_manifest(out3, dc3, workers=[0])
                rng = np.random.default_rng(5)
                rq = int(os.environ.get("BENCH_ROAD_QUERIES", 20_000))
                q3 = np.stack([rng.integers(0, g3.n, rq),
                               rng.integers(0, sub, rq)], axis=1)
                st3 = StreamedCPDOracle(g3, dc3, out3, row_chunk=512,
                                        cache_bytes=4 << 30)
                st3.query(q3[:256])
                st3.clear_cache()
                st3.query(q3)     # prime RLE sidecars untimed (encode
                # is one-time; timed reps below all run the same
                # compressed-index cold path — see the scale section)

                def _cold3():             # cold round pays every upload
                    st3.clear_cache()
                    return st3.query(q3)
                (c3, p3, f3), t_q3_s = robust_time(
                    _cold3,
                    band_s=(8.0 if rn == 264_000 and rq == 20_000
                            else None),
                    label="road-cold-stream")
                assert bool(f3.all())
                road_cold_stats = dict(st3.last_stats)
                (c3w, p3w, f3w), t_q3w = best_of(lambda: st3.query(q3))
                assert st3.last_stats["bytes_streamed"] == 0
                assert (c3w == c3).all()
                log(f"road streamed: cold {rq} in {t_q3_s:.2f}s -> "
                    f"{rq / t_q3_s:,.0f} q/s; warm {t_q3w} -> "
                    f"{rq / t_q3w.interval:,.0f} q/s (chunks cached)")

                # resident worker-0 shard (135 MB) — the per-chip unit
                fm0r = jnp.asarray(blk0)
                est3 = (np.abs(g3.xs[q3[:, 0]] - g3.xs[q3[:, 1]])
                        + np.abs(g3.ys[q3[:, 0]] - g3.ys[q3[:, 1]]))
                o3 = np.argsort(est3, kind="stable")
                qp3 = 1 << (rq - 1).bit_length()
                rr3 = np.zeros(qp3, np.int32)
                ss3 = np.zeros(qp3, np.int32)
                tt3 = np.zeros(qp3, np.int32)
                vv3 = np.zeros(qp3, bool)
                rr3[:rq] = q3[o3, 1]
                ss3[:rq] = q3[o3, 0]
                tt3[:rq] = q3[o3, 1]
                vv3[:rq] = True
                (cr3, pr3, fr3), t_r3 = best_of(
                    lambda: jax.block_until_ready(table_search_batch(
                        dg3, fm0r, rr3, ss3, tt3, dg3.w_pad, valid=vv3)))
                assert bool(np.asarray(fr3)[:rq].all())
                assert (np.asarray(cr3)[np.argsort(o3)] == c3).all()
                rqps3 = rq / t_r3.interval
                t_cq3 = _cpu_query_campaign(
                    bins, xy3, out3, q3, out3, partmethod="div",
                    partkey=sub, workerid=0, maxworker=mw3)
                log(f"road resident: {rq} in {t_r3} -> {rqps3:,.0f} q/s; "
                    f"CPU campaign {t_cq3:.3f}s -> "
                    f"{rq / t_cq3:,.0f} q/s (tpu resident "
                    f"{t_cq3 / t_r3.interval:.2f}x)")

                # congestion round at road scale — the reference campaign
                # shape is one round per diff (process_query.py:178);
                # r03 only ever served roads free-flow. Same queries,
                # perturbed weights, all three servers.
                from distributed_oracle_search_tpu.data import (
                    synth_diff, write_diff,
                )
                dsrc3, ddst3, dw3 = synth_diff(g3, frac=0.1, seed=7)
                w_diff3 = g3.weights_with_diff((dsrc3, ddst3, dw3))
                diff3 = os.path.join(out3, "road.xy.diff")
                write_diff(diff3, dsrc3, ddst3, dw3)
                # streamed diff round: chunks already cached; best_of
                # like every other serve figure (single-shot timings
                # carry the ±20% link jitter). The per-call diff-weight
                # upload stays inside the timer — it IS part of serving
                # a diff round.
                (cd3, pd3, fd3), t_qd3 = best_of(
                    lambda: st3.query(q3, w_query=w_diff3))
                assert bool(fd3.all())
                assert st3.last_stats["bytes_streamed"] == 0, \
                    "diff round must reuse the free-flow round's chunks"
                assert (cd3 >= c3).all(), \
                    "road diffed costs must dominate free flow"
                w_pad3d = jnp.asarray(g3.padded_weights(w_diff3),
                                      jnp.int32)
                (crd3, prd3, frd3), t_rd3 = best_of(
                    lambda: jax.block_until_ready(table_search_batch(
                        dg3, fm0r, rr3, ss3, tt3, w_pad3d, valid=vv3)))
                assert (np.asarray(crd3)[np.argsort(o3)] == cd3).all(), \
                    "road diff: resident and streamed answers differ"
                t_cqd3 = _cpu_query_campaign(
                    bins, xy3, out3, q3, out3, partmethod="div",
                    partkey=sub, workerid=0, maxworker=mw3,
                    difffile=diff3)
                log(f"road diff round: streamed {rq} in {t_qd3} -> "
                    f"{rq / t_qd3.interval:,.0f} q/s; resident {t_rd3} "
                    f"-> {rq / t_rd3.interval:,.0f} q/s; CPU campaign "
                    f"{t_cqd3:.3f}s -> {rq / t_cqd3:,.0f} q/s (tpu "
                    f"resident {t_cqd3 / t_rd3.interval:.2f}x)")

                # fused multi-diff: D congestion rounds in ONE walk
                # (trajectories are diff-independent — the reference
                # must run D sequential rounds, process_query.py:178).
                # All weight rows are pre-uploaded for BOTH paths so
                # the comparison times walks, not uploads.
                from distributed_oracle_search_tpu.ops.table_search \
                    import table_search_multi
                n_rounds = 4
                w4 = [g3.weights_with_diff(synth_diff(
                          g3, frac=0.1, seed=70 + i))
                      for i in range(n_rounds)]
                w4_seq = [jnp.asarray(g3.padded_weights(w), jnp.int32)
                          for w in w4]
                w4_pads = jnp.asarray(
                    np.stack([g3.padded_weights(w) for w in w4]),
                    jnp.int32)

                def seq_rounds():
                    return [jax.block_until_ready(table_search_batch(
                        dg3, fm0r, rr3, ss3, tt3, wd, valid=vv3))
                        for wd in w4_seq]

                def fused_rounds():
                    return jax.block_until_ready(table_search_multi(
                        dg3, fm0r, rr3, ss3, tt3, w4_pads, valid=vv3))

                seq_out, t_seq4 = best_of(seq_rounds)
                (cm4, pm4, fm4), t_fus4 = best_of(fused_rounds)
                for di, (cs, ps, fs) in enumerate(seq_out):
                    assert (np.asarray(cm4[di]) == np.asarray(cs)).all(), \
                        f"fused round {di} != sequential round"
                log(f"road multi-diff: {n_rounds} rounds fused in "
                    f"{t_fus4} vs sequential {t_seq4} "
                    f"({t_seq4.interval / t_fus4.interval:.2f}x; "
                    f"{n_rounds * rq / t_fus4.interval:,.0f} "
                    f"answers/s fused)")

                cores = os.cpu_count() or 1
                road_stats = {
                    "road_nodes": g3.n,
                    "road_edges": g3.m,
                    "road_shift_coverage_raw": round(cov_raw, 4),
                    "road_shift_coverage_rcm": round(cov_rcm, 4),
                    "road_build_kernel": kind3,
                    "road_build_rows": trows,
                    "road_tpu_build_rows_per_sec": round(tpu_rps3, 2),
                    "road_cpu_build_rows_per_sec": round(cpu_rps3, 2),
                    "road_build_parity_cores": round(
                        tpu_rps3 / cpu_rps3 * cores, 2),
                    "road_stream_queries_per_sec": round(
                        rq / t_q3_s, 1),
                    "road_stream_rle_chunks":
                        road_cold_stats["chunks_rle"],
                    "road_stream_sidecar_hits":
                        road_cold_stats["sidecar_hits"],
                    "road_stream_wire_mb": round(
                        road_cold_stats["bytes_streamed"] / 1e6, 1),
                    "road_stream_warm_queries_per_sec": round(
                        rq / t_q3w.interval, 1),
                    "road_resident_queries_per_sec": round(rqps3, 1),
                    "road_cpu_queries_per_sec": round(rq / t_cq3, 1),
                    "road_tpu_resident_speedup": round(
                        t_cq3 / t_r3.interval, 3),
                    "road_diff_stream_queries_per_sec": round(
                        rq / t_qd3.interval, 1),
                    "road_diff_resident_queries_per_sec": round(
                        rq / t_rd3.interval, 1),
                    "road_diff_cpu_queries_per_sec": round(
                        rq / t_cqd3, 1),
                    "road_diff_tpu_resident_speedup": round(
                        t_cqd3 / t_rd3.interval, 3),
                    "road_multidiff_rounds": n_rounds,
                    "road_multidiff_fused_seconds": round(
                        t_fus4.interval, 3),
                    "road_multidiff_sequential_seconds": round(
                        t_seq4.interval, 3),
                    "road_multidiff_fused_speedup": round(
                        t_seq4.interval / t_fus4.interval, 3),
                }
        finally:
            shutil.rmtree(out3, ignore_errors=True)

    # ---- delta builds: incremental CPD refresh for one diff epoch vs a
    # full rebuild on the retimed graph (ROADMAP item 1's second half).
    # Deliberately CPU-measurable: the ratio is work-skipped / work-done
    # — a property of the tense-edge dirty pass and the block byte-copy
    # path, not of the device. The delta timing INCLUDES the dirty-set
    # pass and the manifest write (that is the end-to-end refresh a
    # traffic epoch pays). BENCH_DELTA=0 skips.
    delta_stats = {}
    if os.environ.get("BENCH_DELTA", "1") != "0":
        from distributed_oracle_search_tpu.data import write_diff
        from distributed_oracle_search_tpu.data.graph import (
            Graph as _DGraph,
        )
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, delta_build_index, epoch_index_dir,
            write_index_manifest,
        )

        dside = int(os.environ.get("BENCH_DELTA_SIDE", 48))
        dhot = int(os.environ.get("BENCH_DELTA_HOT", 2))
        gd = synth_city_graph(dside, dside, seed=2)
        wd = 4
        per_wd = -(-gd.n // wd)
        dcd = DistributionController("div", per_wd, wd, gd.n)
        ddir = tempfile.mkdtemp(prefix="dos-delta-")
        try:
            log(f"delta build: n={gd.n}, {wd} shards, {dhot}-edge "
                "congestion hotspot...")
            for wid in range(wd):
                build_worker_shard(gd, dcd, wid, ddir, chunk=512)
            write_index_manifest(ddir, dcd)
            # LOCALIZED retime — a congestion hotspot (edges from one
            # small id window = one spatial pocket after the grid
            # layout, weights doubled), the traffic plane's actual
            # workload shape. A same-size RANDOM scatter on a graph
            # this small marks every row dirty (each edge's co-optimal
            # cone is a few % of a 2k-node graph; dozens of them union
            # to all of it) — that regime is what the
            # DOS_BUILD_DELTA_MAX_FRAC degrade-to-full guard is for,
            # not what this section measures
            rng = np.random.default_rng(13)
            hot_eids = np.nonzero(gd.src < gd.n // 32)[0]
            eids = rng.choice(hot_eids, size=min(dhot, len(hot_eids)),
                              replace=False)
            fused = os.path.join(ddir, "fused-e000001.diff")
            write_diff(fused, gd.src[eids], gd.dst[eids],
                       gd.w[eids].astype(np.int64) * 2)
            g_ret = _DGraph(gd.xs, gd.ys, gd.src, gd.dst,
                            gd.weights_with_diff(fused))

            fdir = os.path.join(ddir, "full")

            def _reset_full():
                shutil.rmtree(fdir, ignore_errors=True)

            def _full():
                for wid in range(wd):
                    build_worker_shard(g_ret, dcd, wid, fdir,
                                       chunk=512, resume=False)
            _reset_full()
            _, t_fullb = robust_time(_full, reset=_reset_full,
                                     label="delta-full-build")

            edir = epoch_index_dir(ddir, 1)

            def _reset_delta():
                shutil.rmtree(edir, ignore_errors=True)

            rep_box = {}

            def _delta():
                rep_box["rep"] = delta_build_index(gd, dcd, ddir, fused,
                                                   resume=False)
            _reset_delta()
            _, t_deltab = robust_time(_delta, reset=_reset_delta,
                                      label="delta-build")
            rep = rep_box["rep"]
            # correctness gate: the incremental index must be BIT-
            # IDENTICAL to the from-scratch build on the retimed graph
            for f in sorted(os.listdir(fdir)):
                if f.startswith("cpd-"):
                    assert (open(os.path.join(edir, f), "rb").read()
                            == open(os.path.join(fdir, f), "rb").read()
                            ), f"delta block {f} != full rebuild"
            ratio = t_fullb / t_deltab
            log(f"delta build: full {t_fullb:.2f}s vs delta "
                f"{t_deltab:.2f}s -> {ratio:.2f}x "
                f"({rep['rows_recomputed']}/{gd.n} rows recomputed, "
                f"{rep['blocks_skipped']} block(s) byte-copied, "
                f"{rep['changed_edges']} edges changed)")
            delta_stats = {
                "build_delta_nodes": gd.n,
                "build_delta_changed_edges": rep["changed_edges"],
                "build_delta_affected_rows": rep["affected_rows"],
                "build_delta_rows_recomputed": rep["rows_recomputed"],
                "build_delta_skipped_blocks": rep["blocks_skipped"],
                "build_full_seconds": round(t_fullb, 3),
                "build_delta_seconds": round(t_deltab, 3),
                "build_full_rows_per_sec": round(gd.n / t_fullb, 1),
                "build_delta_rows_per_sec": round(gd.n / t_deltab, 1),
                "build_delta_vs_full_ratio": round(ratio, 2),
            }
        finally:
            shutil.rmtree(ddir, ignore_errors=True)

    # ---- weak scaling: same total rows over 1/2/4/8 virtual CPU devices,
    # decomposed into mesh wall-clock (oversubscribed: 8 threads on one
    # core) and per-shard single-device time (the per-chip unit; with
    # zero build collectives, W real chips run shards concurrently)
    weak_stats = {}
    if os.environ.get("BENCH_WEAK", "1") != "0":
        log("weak scaling (virtual CPU mesh subprocess)...")
        weak = _weak_scaling(side=64, chunk=512)
        if weak:
            mesh_s, shard_s = weak["mesh"], weak["shard"]
            sbase = shard_s.get("1")
            log("weak scaling mesh build seconds (1-core host, "
                "oversubscribed): " + ", ".join(
                    f"W={w}: {s}s" for w, s in mesh_s.items()))
            log("weak scaling per-shard device seconds (1 worker's rows "
                "on 1 device): " + ", ".join(
                    f"W={w}: {s}s (x{sbase / s:.2f})"
                    for w, s in shard_s.items()))
            weak_stats = {
                "weak_scaling_build_seconds": mesh_s,
                "weak_scaling_shard_device_seconds": shard_s,
                "weak_scaling_shard_rows": weak["rows"],
            }

    # ---- shard strong scaling on the REAL device: one chip builds
    # worker 0's shard of a W-way partition of the main graph. The build
    # HLO has no collectives (pinned by test), so W chips each holding
    # one such shard would run these same programs CONCURRENTLY: the
    # full-build wall-clock on W chips ≈ this measured per-shard time.
    # This is the positive multi-device evidence available without
    # multi-chip hardware.
    if os.environ.get("BENCH_WEAK", "1") != "0":
        from distributed_oracle_search_tpu.models.cpd import (
            _make_chunk_compute, build_worker_shard,
        )

        shard_dev = {}
        shard_rps = {}
        shard_disp = {}
        shard_comp = {}
        shard_over = {}
        # ONE shared compute context across warm-up, every W, and every
        # rep: DeviceGraph upload + build-kernel resolution are
        # per-process setup a resident worker pays once, and re-paying
        # them per timed rep was per-shard overhead polluting the
        # strong-scaling series (the same hoist as PR 11's ledger one)
        bctx = {}
        warm = tempfile.mkdtemp(prefix="dos-shard-warm-")
        try:  # one warm-up build compiles the chunked program
            build_worker_shard(
                g, DistributionController("tpu", None, 8, g.n), 0, warm,
                chunk=chunk, ctx=bctx)
        finally:
            shutil.rmtree(warm, ignore_errors=True)
        for wsh in (1, 2, 4, 8):
            dcw = DistributionController("tpu", None, wsh, g.n)
            d = tempfile.mkdtemp(prefix=f"dos-shard{wsh}-")
            try:
                # stall-guarded like every build: r04's README headline
                # multiplied an anomalously slow single-shot W=1 reading
                def _reset_sh():      # resume would skip existing blocks
                    shutil.rmtree(d)
                    os.makedirs(d)
                # resume=False: the reset guarantees an empty dir, so
                # the ledger read would be pure timed-region overhead
                _, t_sh_s = robust_time(
                    lambda: build_worker_shard(g, dcw, 0, d, chunk=chunk,
                                               resume=False, ctx=bctx),
                    reset=_reset_sh,
                    # ~2x the best r05 readings per W, default knobs only
                    band_s=({1: 4.0, 2: 2.2, 4: 1.4, 8: 0.9}[wsh]
                            if (width, height) == (96, 96) and chunk == 512
                            else None),
                    label=f"shard-w{wsh}")
                shard_dev[str(wsh)] = round(t_sh_s, 3)
                shard_rps[str(wsh)] = round(dcw.n_owned(0) / t_sh_s, 1)
                # dispatch-vs-compute decomposition of the SAME rows:
                # issue every chunk kernel call without blocking
                # (dispatch = host-side call cost), then block (compute
                # = device wall-clock). total-build minus compute is
                # the per-shard overhead — writer fsyncs, ledger lines,
                # fetch/encode — the series that explains WHY rows/s
                # regresses as the per-shard row count shrinks.
                kind_b, st_b = bctx["kernel"]
                compute = _make_chunk_compute(bctx["dg"], kind_b, st_b, 0)
                owned_w = dcw.owned(0)
                pads_w = []
                for off in range(0, len(owned_w), chunk):
                    part = owned_w[off:off + chunk]
                    pad = np.full(chunk, -1, np.int32)
                    pad[:len(part)] = part
                    pads_w.append(pad)
                t0 = time.perf_counter()
                outs = [compute(p) for p in pads_w]
                t_disp = time.perf_counter() - t0
                jax.block_until_ready([dv for dv, _cd in outs])
                t_comp = time.perf_counter() - t0
                shard_disp[str(wsh)] = round(t_disp, 4)
                shard_comp[str(wsh)] = round(t_comp, 4)
                shard_over[str(wsh)] = round(max(t_sh_s - t_comp, 0.0), 4)
            finally:
                shutil.rmtree(d, ignore_errors=True)
        base = shard_dev["1"]
        log("shard strong scaling (real device, worker-0 shard of a "
            "W-way partition): " + ", ".join(
                f"W={w}: {s}s (x{base / s:.2f})"
                for w, s in shard_dev.items()))
        log("shard strong scaling breakdown (dispatch / compute / "
            "overhead s): " + ", ".join(
                f"W={w}: {shard_disp[w]}/{shard_comp[w]}/{shard_over[w]}"
                for w in shard_dev))
        weak_stats["shard_strong_scaling_device_seconds"] = shard_dev
        weak_stats["shard_strong_scaling_rows_per_sec"] = shard_rps
        weak_stats["shard_strong_scaling_dispatch_seconds"] = shard_disp
        weak_stats["shard_strong_scaling_compute_seconds"] = shard_comp
        weak_stats["shard_strong_scaling_overhead_seconds"] = shard_over
        # scalar twins for the bench-diff gate (it compares numbers,
        # not dicts): the W=1/W=8 endpoints pin the strong-scaling
        # trend so the measured regression cannot silently widen
        weak_stats["shard_strong_scaling_rows_per_sec_w1"] = \
            shard_rps["1"]
        weak_stats["shard_strong_scaling_rows_per_sec_w8"] = \
            shard_rps["8"]
        weak_stats["shard_strong_scaling_overhead_w8_seconds"] = \
            shard_over["8"]

        # sharded streamed serving: two controller processes split one
        # streamed campaign's uploads (each streams only its workers'
        # rows; answers merge via allgather). CPU-mesh subprocesses,
        # like the rest of this section.
        from distributed_oracle_search_tpu.models.cpd import (
            write_index_manifest,
        )
        sdir = tempfile.mkdtemp(prefix="dos-shstream-")
        try:
            gs = synth_city_graph(64, 64, seed=0)
            dcs = DistributionController("mod", 4, 4, gs.n)
            for wid in range(4):
                build_worker_shard(gs, dcs, wid, sdir, chunk=256)
            write_index_manifest(sdir, dcs)
            xys = os.path.join(sdir, "g.xy")
            write_xy(xys, gs.xs, gs.ys, gs.src, gs.dst, gs.w)
            qs = synth_scenario(gs.n, 4096, seed=21)
            qf = os.path.join(sdir, "q.npy")
            np.save(qf, np.asarray(qs))
            log("sharded streamed serving (2 CPU controller "
                "processes)...")
            split = _sharded_stream(xys, sdir, qf)
            if split is None:
                log("sharded streamed subprocess failed; skipping field")
            else:
                tot = sum(split)
                log(f"sharded stream: per-process wire bytes {split} "
                    f"(max share {max(split) / tot:.0%} of "
                    f"{tot / 1e6:.1f} MB total)")
                weak_stats["sharded_stream_bytes_per_process"] = split
                weak_stats["sharded_stream_max_share"] = round(
                    max(split) / tot, 3)
        finally:
            shutil.rmtree(sdir, ignore_errors=True)

    # ---- worker mesh: multi-device sharded execution per device count
    # (lane-mesh build, lane-split walk, on-mesh collective mat) on the
    # 8-virtual-CPU-device shim — parity-asserted inside the
    # subprocess. BENCH_MESH=0 skips.
    mesh_stats = {}
    if os.environ.get("BENCH_MESH", "1") != "0":
        log("worker mesh (1/2/4/8 virtual CPU devices, subprocess)...")
        meshr = _mesh_scaling(side=64, chunk=512)
        if meshr:
            mesh_stats = {
                "mesh_build_rows_per_sec": meshr["build"],
                "mesh_walk_queries_per_sec": meshr["walk"],
                "mesh_mat_rows_per_sec": meshr["mat"],
                # scalar twins for the bench-diff gate (dict keys are
                # not compared); d8 = the full-mesh end of each series
                "mesh_build_rows_per_sec_d8": meshr["build"]["8"],
                "mesh_walk_queries_per_sec_d8": meshr["walk"]["8"],
                "mesh_mat_rows_per_sec_d8": meshr["mat"]["8"],
            }
            for name, series in (("build rows/s", meshr["build"]),
                                 ("walk q/s", meshr["walk"]),
                                 ("mat rows/s", meshr["mat"])):
                log(f"mesh {name} (one time-sliced core — overhead "
                    "proxy, not speedup): " + ", ".join(
                        f"L={k}: {v:,.0f}" for k, v in series.items()))

    # ---- multichip smoke: the full sharded pipeline step on an 8-
    # device (data x worker) mesh — previously a detached
    # MULTICHIP_r*.json dryrun artifact, now a recorded bench section
    # so multichip health rides the same bench-diff gate
    # (multichip_smoke_ok is tolerance-0: any 1 -> 0 drop gates).
    # BENCH_MULTICHIP=0 skips.
    multichip_stats = {}
    if os.environ.get("BENCH_MULTICHIP", "1") != "0":
        log("multichip smoke (dryrun_multichip on 8 virtual CPU "
            "devices)...")
        here = os.path.dirname(os.path.abspath(__file__))
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        try:
            res = subprocess.run(
                [sys.executable, os.path.join(here, "__graft_entry__.py"),
                 "8"], cwd=here, env=env, capture_output=True, text=True,
                timeout=900)
            ok = (res.returncode == 0
                  and "dryrun_multichip OK" in res.stdout)
            tail = (res.stdout or res.stderr).strip().splitlines()
            multichip_stats = {
                "multichip_smoke_ok": 1 if ok else 0,
                "multichip_devices": 8,
                "multichip_tail": tail[-1][:200] if tail else "",
            }
        except (subprocess.TimeoutExpired, OSError) as e:
            log(f"multichip smoke failed to run: {e}")
            multichip_stats = {"multichip_smoke_ok": 0,
                               "multichip_devices": 8,
                               "multichip_tail": str(e)[:200]}
        log(f"multichip smoke: "
            f"{'OK' if multichip_stats['multichip_smoke_ok'] else 'FAIL'}"
            f" ({multichip_stats['multichip_tail']})")

    # ---- online serving: open-loop Poisson load against the serving
    # frontend (serving/) backed by the resident oracle — throughput,
    # p50/p95/p99 latency, cache hit rate on a zipf-skewed workload, and
    # the micro-batcher's realized batch fill. Offered load is set to a
    # fraction of MEASURED closed-loop capacity so the figures are
    # comparable across hosts of very different speed. BENCH_SERVE=0
    # skips.
    serve_stats = {}
    if os.environ.get("BENCH_SERVE", "1") != "0":
        from distributed_oracle_search_tpu.obs import (
            metrics as _serve_obs,
        )
        from distributed_oracle_search_tpu.serving import (
            CallableDispatcher, ServeConfig, ServingFrontend,
        )

        log("online serving (Poisson open loop on the resident "
            "oracle)...")
        sb = int(os.environ.get("BENCH_SERVE_BATCH", 256))
        if sb & (sb - 1):
            # ServeConfig requires a pow2 max_batch (compiled-program
            # reuse is the thing being measured); round up, loudly
            sb2 = 1 << (sb - 1).bit_length()
            log(f"BENCH_SERVE_BATCH={sb} is not a power of two; "
                f"using {sb2}")
            sb = sb2
        sn = int(os.environ.get("BENCH_SERVE_REQUESTS", 10_000))
        util = float(os.environ.get("BENCH_SERVE_UTIL", 0.7))
        rng = np.random.default_rng(17)
        pool = queries[rng.zipf(1.3, size=sn).clip(1, len(queries)) - 1]

        def _oracle_dispatch(wid, q, rconf, diff):
            return oracle.query(q)

        # closed-loop capacity: saturate the frontend (submit everything
        # at once) to measure what the shards can actually drain
        sconf = ServeConfig(queue_depth=max(sn, 1024), max_batch=sb,
                            max_wait_ms=2.0, deadline_ms=600_000.0,
                            cache_bytes=0).validate()
        fe = ServingFrontend(dc, CallableDispatcher(_oracle_dispatch),
                             sconf=sconf)
        fe.start()
        for b in (1, sb // 4, sb):            # warm the program shapes
            fe_futs = [fe.submit(int(s), int(t))
                       for s, t in queries[:b]]
            for f in fe_futs:
                f.result(600)
        t0 = time.perf_counter()
        futs = [fe.submit(int(s), int(t)) for s, t in pool]
        for f in futs:
            f.result(600)
        cap_s = time.perf_counter() - t0
        fe.stop()
        capacity_qps = sn / cap_s
        log(f"serve capacity (closed loop): {sn} in {cap_s:.2f}s -> "
            f"{capacity_qps:,.0f} q/s")

        # open loop at util * capacity, cache ON (the skewed workload's
        # steady state), latency measured request-by-request against the
        # Poisson arrival clock
        offered = capacity_qps * util
        snap0 = _serve_obs.REGISTRY.snapshot()
        fe = ServingFrontend(dc, CallableDispatcher(_oracle_dispatch),
                             sconf=ServeConfig(
                                 queue_depth=4096, max_batch=sb,
                                 max_wait_ms=2.0,
                                 deadline_ms=60_000.0).validate())
        fe.start()
        arrivals = np.cumsum(rng.exponential(1.0 / offered, size=sn))
        t0 = time.perf_counter()
        mono0 = time.monotonic()
        futs = []
        for (s, t), at in zip(pool, arrivals):
            now = time.perf_counter() - t0
            if at > now:
                time.sleep(at - now)
            futs.append(fe.submit(int(s), int(t)))
        results = [f.result(600) for f in futs]
        wall_s = time.perf_counter() - t0
        fe.stop()
        lat_ms = (np.array([r.t_done for r in results])
                  - (mono0 + arrivals)) * 1e3
        ok = np.array([r.ok for r in results])
        snap1 = _serve_obs.REGISTRY.snapshot()

        def _cdelta(name):
            return (snap1["counters"].get(name, 0)
                    - snap0["counters"].get(name, 0))

        fill0 = snap0["histograms"]["serve_batch_fill"]
        fill1 = snap1["histograms"]["serve_batch_fill"]
        nb = fill1["count"] - fill0["count"]
        mean_fill = (fill1["sum"] - fill0["sum"]) / max(nb, 1)
        hits = _cdelta("serve_cache_hits_total")
        misses = _cdelta("serve_cache_misses_total")
        # an all-shed/all-error drill must degrade the figures, not
        # crash the run after every earlier section's work
        p50, p95, p99 = ((float(np.percentile(lat_ms[ok], q))
                          for q in (50, 95, 99)) if ok.any()
                         else (float("nan"),) * 3)
        serve_stats = {
            "serve_capacity_queries_per_sec": round(capacity_qps, 1),
            "serve_offered_queries_per_sec": round(offered, 1),
            "serve_queries_per_sec": round(int(ok.sum()) / wall_s, 1),
            "serve_p50_ms": round(p50, 3),
            "serve_p95_ms": round(p95, 3),
            "serve_p99_ms": round(p99, 3),
            "serve_shed": int(len(results) - ok.sum()),
            "serve_cache_hit_rate": round(hits / max(hits + misses, 1),
                                          3),
            "serve_mean_batch_fill": round(mean_fill, 1),
            "serve_batches": int(nb),
        }
        log(f"serve open loop at {offered:,.0f} q/s offered: "
            f"{serve_stats['serve_queries_per_sec']:,.0f} q/s served, "
            f"p50/p95/p99 {p50:.2f}/{p95:.2f}/{p99:.2f} ms, "
            f"cache hit rate {serve_stats['serve_cache_hit_rate']:.0%}, "
            f"mean batch fill {mean_fill:.1f}, "
            f"shed {serve_stats['serve_shed']}")

    # ---- transport section: the streaming RPC data plane vs the FIFO
    # wire, head to head on the SAME worker, engine, and workload —
    # per-batch dispatch overhead (wall minus pure engine time), p99,
    # and throughput for each lane. One in-thread FifoServer serves
    # both transports (the FIFO loop and the socket accept loop share
    # the engine), so the delta is pure transport cost: query-file
    # write + bash transfer script + two FIFO rendezvous + results
    # sidecar read vs one frame round-trip. BENCH_RPC=0 skips.
    rpc_stats = {}
    if os.environ.get("BENCH_RPC", "1") != "0":
        import threading as _threading

        import distributed_oracle_search_tpu.serving.dispatch as _dmod
        from distributed_oracle_search_tpu.data import (
            ensure_synth_dataset, read_scen,
        )
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.serving import (
            FifoDispatcher, RpcDispatcher,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )
        from distributed_oracle_search_tpu.worker import (
            FifoServer, stop_server,
        )
        from distributed_oracle_search_tpu.worker.server import (
            RpcServeLoop,
        )

        log("transport (rpc vs fifo dispatch, one worker, same "
            "workload)...")
        tdir = tempfile.mkdtemp(prefix="bench-rpc-")
        _old_sockdir = os.environ.get("DOS_RPC_SOCKET_DIR")
        os.environ["DOS_RPC_SOCKET_DIR"] = tdir
        tpaths = ensure_synth_dataset(tdir, width=24, height=18,
                                      n_queries=512, seed=37)
        tconf = ClusterConfig(
            workers=["localhost"], partmethod="mod", partkey=1,
            outdir=os.path.join(tdir, "index"), xy_file=tpaths["xy"],
            scenfile=tpaths["scen"], nfs=tdir).validate()
        tg = Graph.from_xy(tconf.xy_file)
        tdc = DistributionController("mod", 1, 1, tg.n)
        build_worker_shard(tg, tdc, 0, tconf.outdir)
        write_index_manifest(tconf.outdir, tdc)
        tqueries = read_scen(tconf.scenfile)
        tfifo = os.path.join(tdir, "worker0.fifo")
        tsrv = FifoServer(tconf, 0, command_fifo=tfifo)
        tth = _threading.Thread(target=tsrv.serve_forever, daemon=True)
        tth.start()
        for _ in range(200):
            if os.path.exists(tfifo):
                break
            time.sleep(0.02)
        tloop = RpcServeLoop(tsrv).start()
        nb = int(os.environ.get("BENCH_RPC_BATCHES", 48))
        bsz = int(os.environ.get("BENCH_RPC_BATCH", 64))
        tbatches = [tqueries[(i * bsz) % len(tqueries):][:bsz]
                    for i in range(nb)]
        tbatches = [b if len(b) == bsz else tqueries[:bsz]
                    for b in tbatches]
        trc = RuntimeConfig()
        fifo_disp = FifoDispatcher(tconf, timeout=120.0)
        rpc_disp = RpcDispatcher(tconf, timeout=120.0)
        orig_cfp = _dmod.command_fifo_path
        _dmod.command_fifo_path = lambda wid: tfifo
        try:
            # warm every lane + the engine's compiled programs off the
            # clock (a mid-run XLA compile would charge one transport)
            fifo_disp.answer_batch(0, tbatches[0], trc, "-")
            rpc_disp.answer_batch(0, tbatches[0], trc, "-")
            tsrv.engine.answer(tbatches[0], trc, "-")

            def _drive(step):
                lat = []
                t0 = time.perf_counter()
                for b in tbatches:
                    s = time.perf_counter()
                    step(b)
                    lat.append(time.perf_counter() - s)
                return time.perf_counter() - t0, np.array(lat)

            eng_wall, eng_lat = _drive(
                lambda b: tsrv.engine.answer(b, trc, "-"))
            rpc_wall, rpc_lat = _drive(
                lambda b: rpc_disp.answer_batch(0, b, trc, "-"))
            fifo_wall, fifo_lat = _drive(
                lambda b: fifo_disp.answer_batch(0, b, trc, "-"))
        finally:
            _dmod.command_fifo_path = orig_cfp
            rpc_disp.close()
            fifo_disp.close()
            stop_server(tfifo, deadline_s=5.0)
            tth.join(timeout=15)
            tloop.stop()
            shutil.rmtree(tdir, ignore_errors=True)
            # restore the socket-dir knob: a later section's supervisor
            # must not resolve sockets under the deleted temp dir
            if _old_sockdir is None:
                os.environ.pop("DOS_RPC_SOCKET_DIR", None)
            else:
                os.environ["DOS_RPC_SOCKET_DIR"] = _old_sockdir
        eng_ms = float(eng_lat.mean() * 1e3)
        rpc_over = float(max(rpc_lat.mean() * 1e3 - eng_ms, 1e-3))
        fifo_over = float(max(fifo_lat.mean() * 1e3 - eng_ms, 1e-3))
        rpc_stats = {
            # per-batch dispatch OVERHEAD: mean wall minus the pure
            # engine time for the identical batch sequence
            "serve_rpc_dispatch_ms": round(rpc_over, 3),
            "serve_fifo_dispatch_ms": round(fifo_over, 3),
            "serve_rpc_vs_fifo_dispatch_ratio": round(
                fifo_over / rpc_over, 2),
            "serve_rpc_p99_ms": round(
                float(np.percentile(rpc_lat, 99)) * 1e3, 3),
            "serve_fifo_p99_ms": round(
                float(np.percentile(fifo_lat, 99)) * 1e3, 3),
            "serve_rpc_queries_per_sec": round(
                nb * bsz / rpc_wall, 1),
            "serve_fifo_queries_per_sec": round(
                nb * bsz / fifo_wall, 1),
        }
        log(f"transport: engine {eng_ms:.2f} ms/batch; rpc overhead "
            f"{rpc_over:.2f} ms/batch "
            f"(p99 {rpc_stats['serve_rpc_p99_ms']:.1f} ms), fifo "
            f"overhead {fifo_over:.2f} ms/batch "
            f"(p99 {rpc_stats['serve_fifo_p99_ms']:.1f} ms) -> "
            f"ratio {rpc_stats['serve_rpc_vs_fifo_dispatch_ratio']}x, "
            f"{rpc_stats['serve_rpc_queries_per_sec']:,.0f} vs "
            f"{rpc_stats['serve_fifo_queries_per_sec']:,.0f} q/s")

    # ---- gateway tier section: rush hour on the binary client
    # protocol — two stateless frontend replicas over the SAME worker
    # (gateway/ frames, credit windows, per-replica L1 + shard-owner
    # L2) vs the single-head line-protocol serve on one zipf-skewed
    # pool. Reports aggregate q/s, per-frontend fairness (max/min),
    # and the fleet's two-level cache hit rate vs the single head's;
    # answers must be bit-identical between the lanes. BENCH_GATEWAY=0
    # skips.
    gateway_stats = {}
    if os.environ.get("BENCH_GATEWAY", "1") != "0":
        import queue as _gqueue
        import socket as _gsocket
        import threading as _gthreading

        from distributed_oracle_search_tpu.data import (
            ensure_synth_dataset, read_scen,
        )
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.gateway import (
            DosClient, GatewayConfig, GatewayTier,
        )
        from distributed_oracle_search_tpu.gateway import (
            client as gateway_client,
        )
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.serving import (
            RpcDispatcher, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.serving import ingress
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )
        from distributed_oracle_search_tpu.worker import (
            FifoServer, stop_server,
        )
        from distributed_oracle_search_tpu.worker.server import (
            RpcServeLoop,
        )

        log("gateway tier (2 binary-protocol frontends vs single-head "
            "line protocol, same worker)...")
        gdir = tempfile.mkdtemp(prefix="bench-gw-")
        _genv = {k: os.environ.get(k) for k in
                 ("DOS_RPC_SOCKET_DIR", "DOS_GATEWAY_L2_BYTES")}
        os.environ["DOS_RPC_SOCKET_DIR"] = gdir
        os.environ["DOS_GATEWAY_L2_BYTES"] = str(1 << 20)
        gpaths = ensure_synth_dataset(gdir, width=24, height=18,
                                      n_queries=512, seed=41)
        gcfg = ClusterConfig(
            workers=["localhost"], partmethod="mod", partkey=1,
            outdir=os.path.join(gdir, "index"), xy_file=gpaths["xy"],
            scenfile=gpaths["scen"], nfs=gdir).validate()
        gg = Graph.from_xy(gcfg.xy_file)
        gdc = DistributionController("mod", 1, 1, gg.n)
        build_worker_shard(gg, gdc, 0, gcfg.outdir)
        write_index_manifest(gcfg.outdir, gdc)
        gqueries = read_scen(gcfg.scenfile)
        gfifo = os.path.join(gdir, "gw-worker0.fifo")
        gwsrv = FifoServer(gcfg, 0, command_fifo=gfifo)
        gwth = _gthreading.Thread(target=gwsrv.serve_forever,
                                  daemon=True)
        gwth.start()
        for _ in range(200):
            if os.path.exists(gfifo):
                break
            time.sleep(0.02)
        gloop = RpcServeLoop(gwsrv).start()
        grc = RuntimeConfig()
        gn = int(os.environ.get("BENCH_GATEWAY_REQUESTS", 4096))
        gb = int(os.environ.get("BENCH_GATEWAY_BATCH", 64))
        grng = np.random.default_rng(23)
        gpool = gqueries[grng.zipf(1.3, size=gn)
                         .clip(1, len(gqueries)) - 1]
        # warm the worker engine's compiled shapes off every clock
        gwsrv.engine.answer(gqueries[:gb], grc, "-")

        def _gfe():
            fe = ServingFrontend(
                gdc, RpcDispatcher(gcfg, timeout=120.0),
                sconf=ServeConfig(queue_depth=max(gn, 1024),
                                  max_batch=gb, max_wait_ms=2.0,
                                  deadline_ms=600_000.0,
                                  cache_bytes=1 << 20).validate())
            fe.start()
            return fe

        def _line_row(line):
            # OK <s> <t> <cost> <plen> <finished> [cached]
            toks = line.split()
            if len(toks) >= 6 and toks[0] == "OK":
                return (toks[0], int(toks[3]), int(toks[4]),
                        bool(int(toks[5])))
            return (toks[0] if toks else "ERROR", -1, -1, False)

        gclients = []
        tier = None
        gfes = []
        try:
            # -- single head: the legacy line-protocol lane, fully
            # pipelined (writer thread keeps lines flowing while the
            # replies stream back in order)
            fe0 = _gfe()
            gfes.append(fe0)
            glsock = os.path.join(gdir, "line.sock")
            glstop = _gthreading.Event()
            glth = _gthreading.Thread(
                target=ingress.serve_unix_socket, args=(fe0, glsock),
                kwargs={"stop": glstop}, daemon=True)
            glth.start()
            for _ in range(200):
                if os.path.exists(glsock):
                    break
                time.sleep(0.02)
            gcs = _gsocket.socket(_gsocket.AF_UNIX,
                                  _gsocket.SOCK_STREAM)
            gcs.connect(glsock)
            gcrf = gcs.makefile("r")
            gcwf = gcs.makefile("w")

            def _drive_line(part):
                def _pump():
                    for s, t in part:
                        gcwf.write(f"{int(s)} {int(t)}\n")
                    gcwf.flush()

                rows = []
                w = _gthreading.Thread(target=_pump, daemon=True)
                t0 = time.perf_counter()
                w.start()
                for _ in range(len(part)):
                    rows.append(_line_row(gcrf.readline()))
                w.join()
                return time.perf_counter() - t0, rows

            _drive_line(gpool[:gb])          # warm lane + L1 + shapes
            h0, m0 = fe0.cache.hits, fe0.cache.misses
            l2h0, l2m0 = gwsrv.l2.hits, gwsrv.l2.misses
            single_wall, base_rows = _drive_line(gpool)
            single_hits = ((fe0.cache.hits - h0)
                           + (gwsrv.l2.hits - l2h0))
            gcwf.write("quit\n")
            gcwf.flush()
            gcs.close()
            glstop.set()
            glth.join(timeout=10)
            fe0.stop()

            # -- the tier: 2 replicas, 2 clients, batched query frames.
            # The single head's L2 entries are flushed first — the
            # fleet hit rate must be earned by THIS lane's traffic
            gwsrv.l2.invalidate()
            gfes = [fe0] + [_gfe() for _ in range(2)]
            fes = gfes[1:]
            ggconf = GatewayConfig(
                replicas=2, socket_dir=gdir, credit=64,
                deadline_ms=600_000.0).validate()
            tier = GatewayTier([(fe, None) for fe in fes],
                               gconf=ggconf).start()
            gclients = [DosClient(ep) for ep in tier.endpoints]
            ghalves = [gpool[0::2], gpool[1::2]]
            for c, half in zip(gclients, ghalves):   # warm, off-clock
                c.query_batch([(int(s), int(t)) for s, t in half[:gb]],
                              timeout=600.0)
            gh0 = [(fe.cache.hits, fe.cache.misses) for fe in fes]
            gl2h0 = gwsrv.l2.hits
            gwalls = [0.0, 0.0]
            grows = [[], []]

            def _drive_gw(k):
                # open loop: a pump thread keeps the credit window
                # full while this thread collects replies in
                # submission order — the frame-level twin of the line
                # lane's pipelined writer
                c, half = gclients[k], ghalves[k]
                fidq = _gqueue.Queue()

                def _pump():
                    for i in range(0, len(half), gb):
                        batch = [(int(s), int(t))
                                 for s, t in half[i:i + gb]]
                        fidq.put(c.submit_pairs(batch, timeout=600.0))
                    fidq.put(None)

                t0 = time.perf_counter()
                w = _gthreading.Thread(target=_pump, daemon=True)
                w.start()
                while True:
                    fid = fidq.get()
                    if fid is None:
                        break
                    rows = gateway_client.pair_rows(
                        c.wait(fid, timeout=600.0))
                    grows[k].extend((st, cost, plen, fin) for st, cost,
                                    plen, fin, _cached in rows)
                w.join()
                gwalls[k] = time.perf_counter() - t0

            gths = [_gthreading.Thread(target=_drive_gw, args=(k,))
                    for k in range(2)]
            t0 = time.perf_counter()
            for th in gths:
                th.start()
            for th in gths:
                th.join()
            tier_wall = time.perf_counter() - t0
            fleet_hits = (sum(fe.cache.hits - h for fe, (h, _m)
                              in zip(fes, gh0))
                          + (gwsrv.l2.hits - gl2h0))
        finally:
            for c in gclients:
                c.close()
            if tier is not None:
                tier.stop()
            for fe in gfes[1:]:
                fe.stop()
            stop_server(gfifo, deadline_s=5.0)
            gwth.join(timeout=15)
            gloop.stop()
            shutil.rmtree(gdir, ignore_errors=True)
            for k, v in _genv.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        gw_rows = [None] * gn
        gw_rows[0::2] = grows[0]
        gw_rows[1::2] = grows[1]
        matches = sum(a == b for a, b in zip(base_rows, gw_rows))
        per_fe_qps = [len(h) / max(w, 1e-9)
                      for h, w in zip(ghalves, gwalls)]
        gateway_stats = {
            "gateway_aggregate_queries_per_sec": round(
                gn / tier_wall, 1),
            "gateway_single_head_queries_per_sec": round(
                gn / single_wall, 1),
            "gateway_vs_single_head_ratio": round(
                single_wall / tier_wall, 2),
            "gateway_fairness_ratio": round(
                max(per_fe_qps) / max(min(per_fe_qps), 1e-9), 2),
            "gateway_answers_match": round(matches / gn, 4),
            "gateway_fleet_cache_hit_rate": round(fleet_hits / gn, 3),
            "gateway_single_head_cache_hit_rate": round(
                single_hits / gn, 3),
        }
        log(f"gateway: tier "
            f"{gateway_stats['gateway_aggregate_queries_per_sec']:,.0f}"
            f" q/s vs single head "
            f"{gateway_stats['gateway_single_head_queries_per_sec']:,.0f}"
            f" q/s ({gateway_stats['gateway_vs_single_head_ratio']}x), "
            f"fairness {gateway_stats['gateway_fairness_ratio']}x, "
            f"answers match {gateway_stats['gateway_answers_match']:.2%}"
            f", fleet cache "
            f"{gateway_stats['gateway_fleet_cache_hit_rate']:.0%} vs "
            f"single "
            f"{gateway_stats['gateway_single_head_cache_hit_rate']:.0%}")

    # ---- gateway HA section: the partition chaos drill priced as a
    # bench — one HA client (registry discovery) drives an open-loop
    # burst over a 3-frontend leased tier while one frontend is killed
    # abruptly and a second goes half-open (blackhole-conn). The
    # contract under test: zero lost accepted requests, zero duplicate
    # answers (resubmission dedup), and failover recovery bounded by
    # the detection timeout + reconnect. BENCH_GATEWAY_HA=0 skips.
    gateway_ha_stats = {}
    if os.environ.get("BENCH_GATEWAY_HA", "1") != "0":
        import queue as _hqueue
        import socket as _hsocket  # noqa: F401 — parity with gw block
        import threading as _hthreading

        from distributed_oracle_search_tpu.data import (
            ensure_synth_dataset, read_scen,
        )
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.gateway import (
            DosClient, GatewayConfig, GatewayRegistry, GatewayTier,
        )
        from distributed_oracle_search_tpu.gateway import (
            client as gateway_client,
        )
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.serving import (
            RpcDispatcher, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.testing import faults
        from distributed_oracle_search_tpu.transport.frames import (
            TransportError,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )
        from distributed_oracle_search_tpu.worker import (
            FifoServer, stop_server,
        )
        from distributed_oracle_search_tpu.worker.server import (
            RpcServeLoop,
        )

        log("gateway HA (kill + blackhole mid-burst over a 3-frontend "
            "leased tier, one failover client)...")
        hdir = tempfile.mkdtemp(prefix="bench-gwha-")
        _henv = {k: os.environ.get(k) for k in
                 ("DOS_RPC_SOCKET_DIR", "DOS_FAULTS")}
        os.environ["DOS_RPC_SOCKET_DIR"] = hdir
        os.environ.pop("DOS_FAULTS", None)
        hpaths = ensure_synth_dataset(hdir, width=16, height=12,
                                      n_queries=256, seed=47)
        hcfg = ClusterConfig(
            workers=["localhost"], partmethod="mod", partkey=1,
            outdir=os.path.join(hdir, "index"), xy_file=hpaths["xy"],
            scenfile=hpaths["scen"], nfs=hdir).validate()
        hg = Graph.from_xy(hcfg.xy_file)
        hdc = DistributionController("mod", 1, 1, hg.n)
        build_worker_shard(hg, hdc, 0, hcfg.outdir)
        write_index_manifest(hcfg.outdir, hdc)
        hqueries = read_scen(hcfg.scenfile)
        hfifo = os.path.join(hdir, "ha-worker0.fifo")
        hwsrv = FifoServer(hcfg, 0, command_fifo=hfifo)
        hwth = _hthreading.Thread(target=hwsrv.serve_forever,
                                  daemon=True)
        hwth.start()
        for _ in range(200):
            if os.path.exists(hfifo):
                break
            time.sleep(0.02)
        hloop = RpcServeLoop(hwsrv).start()
        hrc = RuntimeConfig()
        hn = int(os.environ.get("BENCH_GATEWAY_HA_REQUESTS", 2048))
        hb = int(os.environ.get("BENCH_GATEWAY_HA_BATCH", 64))
        hrng = np.random.default_rng(29)
        hpool = hqueries[hrng.zipf(1.3, size=hn)
                         .clip(1, len(hqueries)) - 1]
        hwsrv.engine.answer(hqueries[:hb], hrc, "-")   # warm shapes

        def _hfe():
            fe = ServingFrontend(
                hdc, RpcDispatcher(hcfg, timeout=120.0),
                sconf=ServeConfig(queue_depth=max(hn, 1024),
                                  max_batch=hb, max_wait_ms=2.0,
                                  deadline_ms=600_000.0,
                                  cache_bytes=0).validate())
            fe.start()
            return fe

        hclient = None
        htier = None
        hfes = []
        try:
            hfes = [_hfe() for _ in range(3)]
            hreg = GatewayRegistry(os.path.join(hdir, "reg"),
                                   lease_s=1.0)
            hgconf = GatewayConfig(
                replicas=3, socket_dir=hdir, credit=64,
                deadline_ms=600_000.0, lease_s=1.0).validate()
            htier = GatewayTier([(fe, None) for fe in hfes],
                                gconf=hgconf, registry=hreg).start()
            # fault-free baseline over the SAME pool: the drill's
            # answers must be bit-identical to these rows
            hbase_client = DosClient(htier.endpoints[2])
            hbase_rows = []
            for i in range(0, hn, hb):
                hbase_rows.extend(
                    (st, cost, plen, fin) for st, cost, plen, fin,
                    _c in hbase_client.query_batch(
                        [(int(s), int(t)) for s, t in hpool[i:i + hb]],
                        timeout=600.0))
            hbase_client.close()

            hclient = DosClient(registry_dir=hreg.dir)   # discovery
            nbatches = (hn + hb - 1) // hb
            kill_at, hole_at = nbatches // 3, (2 * nbatches) // 3
            hfidq = _hqueue.Queue()

            def _hpump():
                try:
                    for bi in range(nbatches):
                        if bi == kill_at:
                            # abrupt death: lease left to expire
                            htier.servers[0].stop(graceful=False)
                        if bi == hole_at:
                            # half-open partition on whichever
                            # frontend the client failed over to (f1,
                            # next in discovery order)
                            os.environ["DOS_FAULTS"] = \
                                "blackhole-conn;wid=1;times=inf"
                            faults.reset()
                        batch = [
                            (int(s), int(t))
                            for s, t in hpool[bi * hb:(bi + 1) * hb]]
                        hfidq.put((bi, hclient.submit_pairs(
                            batch, timeout=600.0),
                            time.perf_counter()))
                finally:
                    hfidq.put(None)

            hrows_by_batch = {}
            hlat_ms = []
            hw = _hthreading.Thread(target=_hpump, daemon=True)
            hw.start()
            while True:
                item = hfidq.get()
                if item is None:
                    break
                bi, fid, t_sub = item
                give_up = time.perf_counter() + 120.0
                got = None
                while got is None:
                    try:
                        got = gateway_client.pair_rows(
                            hclient.wait(fid, timeout=2.0))
                    except TimeoutError:
                        # wait's own timeout already failed the
                        # client over and resubmitted; re-wait
                        # collects the replayed answer
                        if time.perf_counter() > give_up:
                            break
                    except TransportError:
                        break
                if got is None:
                    continue
                hlat_ms.append((time.perf_counter() - t_sub) * 1e3)
                hrows_by_batch[bi] = [(st, cost, plen, fin)
                                      for st, cost, plen, fin, _c
                                      in got]
            hw.join()
            # per-batch accounting so a dropped batch can't misalign
            # the comparison: a never-answered request is lost; an
            # answered-but-wrong request counts as lost too (the HA
            # contract is bit-identical answers, tolerance 0)
            hlost = 0
            hmatch = 0
            for bi in range(nbatches):
                base = hbase_rows[bi * hb:(bi + 1) * hb]
                rows = hrows_by_batch.get(bi)
                if rows is None:
                    hlost += len(base)
                    continue
                ok = sum(a == b for a, b in zip(base, rows))
                hmatch += ok
                hlost += len(base) - ok
            hp99 = (float(np.percentile(np.asarray(hlat_ms), 99))
                    if hlat_ms else float("nan"))
            gateway_ha_stats = {
                "gateway_ha_lost_requests": int(hlost),
                "gateway_ha_duplicate_answers": int(hclient.unmatched),
                "gateway_ha_failover_p99_ms": round(hp99, 1),
            }
            log(f"gateway HA: lost "
                f"{gateway_ha_stats['gateway_ha_lost_requests']}, "
                f"duplicates "
                f"{gateway_ha_stats['gateway_ha_duplicate_answers']}, "
                f"p99 {gateway_ha_stats['gateway_ha_failover_p99_ms']}"
                f" ms across {hclient.failovers} failover(s), "
                f"answers match {hmatch}/{hn}")
        finally:
            if hclient is not None:
                hclient.close()
            os.environ.pop("DOS_FAULTS", None)
            faults.reset()
            if htier is not None:
                htier.stop()
            for fe in hfes:
                fe.stop()
            stop_server(hfifo, deadline_s=5.0)
            hwth.join(timeout=15)
            hloop.stop()
            shutil.rmtree(hdir, ignore_errors=True)
            for k, v in _henv.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # ---- telemetry section: the fleet telemetry bus priced in
    # isolation — publish-side tick cost (what the bus adds to every
    # resident process each DOS_TELEMETRY_INTERVAL_S; the acceptance
    # bar is overhead < 1% of the interval) and the head's ingest rate
    # into the ring store (decode + seq dedupe + delta clamp + store
    # appends per tick). In-process on purpose: the wire itself is the
    # transport section's story — this prices the bus machinery on the
    # REAL registry this bench run populated (hundreds of live series,
    # the fleet-realistic key count). BENCH_TELEMETRY=0 skips.
    telemetry_stats = {}
    if os.environ.get("BENCH_TELEMETRY", "1") != "0":
        from distributed_oracle_search_tpu.obs import (
            telemetry as _tele,
        )
        from distributed_oracle_search_tpu.obs import (
            timeseries as _tts,
        )

        log("telemetry (publish overhead + head ingest rate)...")
        n_ticks = int(os.environ.get("BENCH_TELEMETRY_TICKS", 400))
        pub = _tele.TelemetryPublisher("bench", sinks=[])
        pub.tick_once()               # first tick is full — warm it
        tick_s = []
        for _ in range(n_ticks):
            s = time.perf_counter()
            pub.tick_once()
            tick_s.append(time.perf_counter() - s)
        tick_s = np.array(tick_s)
        # head side: replay encoded ticks (the wire's view) from 8
        # simulated sources into a fresh store — per-source seqs
        # strictly increase, so every tick is accepted, none deduped
        tstore = _tts.TimeseriesStore()
        tingest = _tele.TelemetryIngest(tstore)
        wire_ticks = []
        for i in range(n_ticks):
            t = dict(pub.tick_once(),
                     source=f"bench-w{i % 8}", seq=i // 8)
            wire_ticks.append(_tele.encode_tick(t))
        s = time.perf_counter()
        accepted = sum(tingest.ingest(t) for t in wire_ticks)
        ingest_wall = max(time.perf_counter() - s, 1e-9)
        interval = max(pub.interval, 1e-3)
        telemetry_stats = {
            "telemetry_publish_p99_ms": round(
                float(np.percentile(tick_s, 99)) * 1e3, 3),
            # mean tick cost / publish cadence: the fraction of every
            # resident process the bus consumes (acceptance: < 0.01)
            "telemetry_publish_overhead_frac": round(
                float(tick_s.mean()) / interval, 6),
            "telemetry_head_ingest_per_sec": round(
                accepted / ingest_wall, 1),
        }
        log(f"telemetry: publish "
            f"{float(tick_s.mean()) * 1e3:.3f} ms/tick mean "
            f"(p99 {telemetry_stats['telemetry_publish_p99_ms']:.3f} "
            f"ms) = {telemetry_stats['telemetry_publish_overhead_frac']:.4%} "
            f"of the {interval:.0f}s cadence; head ingest "
            f"{telemetry_stats['telemetry_head_ingest_per_sec']:,.0f} "
            f"ticks/s ({accepted}/{n_ticks} accepted)")

    # ---- replication section: failover throughput/latency with a
    # killed primary, and hedge win rate under an injected delay fault.
    # A small dedicated 2-worker R=2 host-style world (block files +
    # EngineDispatcher) — the figures characterize the routing layer,
    # not the kernels, so a small graph keeps it honest and cheap.
    # BENCH_REPL=0 skips.
    repl_stats = {}
    if os.environ.get("BENCH_REPL", "1") != "0":
        from distributed_oracle_search_tpu.data import (
            ensure_synth_dataset, read_scen,
        )
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.models.cpd import (
            build_replica_shards, build_worker_shard,
            write_index_manifest,
        )
        from distributed_oracle_search_tpu.obs import (
            metrics as _robs,
        )
        from distributed_oracle_search_tpu.serving import (
            EngineDispatcher, HedgeConfig, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.transport import resilience
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )

        def _rc(name):
            return _robs.REGISTRY.snapshot()["counters"].get(name, 0)

        log("replication (failover + hedged dispatch drills)...")
        rdir = tempfile.mkdtemp(prefix="bench-repl-")
        rpaths = ensure_synth_dataset(rdir, width=24, height=18,
                                      n_queries=512, seed=31)
        rconf_c = ClusterConfig(
            workers=["localhost"] * 2, partmethod="mod", partkey=2,
            outdir=os.path.join(rdir, "index"),
            xy_file=rpaths["xy"], scenfile=rpaths["scen"], nfs=rdir,
            replication=2).validate()
        rg = Graph.from_xy(rconf_c.xy_file)
        rdc = DistributionController("mod", 2, 2, rg.n, replication=2)
        for wid in range(2):
            build_worker_shard(rg, rdc, wid, rconf_c.outdir)
            build_replica_shards(rg, rdc, wid, rconf_c.outdir)
        write_index_manifest(rconf_c.outdir, rdc)
        rqueries = read_scen(rconf_c.scenfile)
        rn = int(os.environ.get("BENCH_REPL_REQUESTS", 512))
        pool = rqueries[np.arange(rn) % len(rqueries)]
        rrconf = RuntimeConfig()
        disp = EngineDispatcher(rconf_c, graph=rg, dc=rdc)
        # warm every engine (primary + replica lanes) off the clock
        for wid in range(2):
            mine = rqueries[rdc.worker_of(rqueries[:, 1]) == wid][:64]
            disp.answer_batch(wid, mine, rrconf, "-")
            disp.answer_batch(wid, mine, rrconf, "-",
                              via=(wid + 1) % 2)

        def _drill(registry, hconf, tag):
            """Closed-loop drill: submit the pool, wait for every
            answer; per-request latency measured submit -> t_done."""
            fe = ServingFrontend(
                rdc, disp,
                sconf=ServeConfig(max_batch=64, max_wait_ms=2.0,
                                  queue_depth=max(rn, 1024),
                                  cache_bytes=0,
                                  deadline_ms=600_000.0),
                registry=registry, hconf=hconf)
            fe.start()
            t0 = time.perf_counter()
            submits, futs = [], []
            for s, t in pool:
                submits.append(time.monotonic())
                futs.append(fe.submit(int(s), int(t)))
            res = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            fe.stop()
            n_ok = sum(r.ok for r in res)
            lat_ms = [(r.t_done - ts) * 1e3
                      for r, ts in zip(res, submits) if r.ok]
            p99 = float(np.percentile(lat_ms, 99)) if lat_ms else float(
                "nan")
            log(f"  {tag}: {n_ok}/{rn} ok in {wall:.2f}s "
                f"({n_ok / wall:,.0f} q/s, p99 {p99:.1f} ms)")
            return n_ok, wall, p99

        # clean baseline (no failures, hedging off)
        ok_clean, wall_clean, p99_clean = _drill(
            None, HedgeConfig(enabled=False), "clean")
        # failover: worker 0's breaker forced OPEN — every shard-0
        # batch re-routes to worker 1's replica
        f0 = _rc("failover_total")
        reg = resilience.BreakerRegistry(threshold=1, cooldown_s=600.0,
                                         enabled=True)
        reg.record(0, ok=False)
        ok_fo, wall_fo, p99_fo = _drill(
            reg, HedgeConfig(enabled=False), "failover (primary dead)")
        reg.shutdown()
        failovers = _rc("failover_total") - f0

        # hedge drill: the primary lane of shard 0 answers slowly (the
        # in-process analog of the `delay` fault); hedges should win
        class _SlowPrimary:
            def __init__(self, inner, slow_wid, delay_s):
                self.inner, self.slow, self.d = inner, slow_wid, delay_s

            def answer_batch(self, wid, q, rc_, diff, via=None):
                if (wid if via is None else via) == self.slow:
                    time.sleep(self.d)
                return self.inner.answer_batch(wid, q, rc_, diff,
                                               via=via)

        hi0, hw0 = _rc("hedges_issued_total"), _rc("hedges_won_total")
        hbudget = float(os.environ.get("BENCH_REPL_HEDGE_BUDGET", 0.5))
        fe_h = ServingFrontend(
            rdc, _SlowPrimary(disp, 0, 0.05),
            sconf=ServeConfig(max_batch=64, max_wait_ms=1.0,
                              queue_depth=1024, cache_bytes=0,
                              deadline_ms=600_000.0),
            hconf=HedgeConfig(enabled=True, min_delay_ms=5.0,
                              budget=hbudget))
        fe_h.start()
        hpool = pool[:min(rn, 256)]
        t0 = time.perf_counter()
        hres = [fe_h.query(int(s), int(t), timeout=600)
                for s, t in hpool]
        wall_h = time.perf_counter() - t0
        fe_h.stop()
        time.sleep(0.3)          # drain loser primary threads
        hedges = _rc("hedges_issued_total") - hi0
        wins = _rc("hedges_won_total") - hw0
        repl_stats = {
            "repl_clean_queries_per_sec": round(ok_clean / wall_clean,
                                                1),
            "repl_clean_p99_ms": round(p99_clean, 3),
            "repl_failover_queries_per_sec": round(ok_fo / wall_fo, 1),
            "repl_failover_p99_ms": round(p99_fo, 3),
            "repl_failover_ok": int(ok_fo),
            "repl_failover_total": int(failovers),
            "repl_hedges_issued": int(hedges),
            "repl_hedges_won": int(wins),
            "repl_hedge_win_rate": round(wins / max(hedges, 1), 3),
            "repl_hedge_rate": round(fe_h.hedge.hedge_rate(), 3),
            "repl_hedged_queries_per_sec": round(
                sum(r.ok for r in hres) / wall_h, 1),
        }
        log(f"replication: clean "
            f"{repl_stats['repl_clean_queries_per_sec']:,.0f} q/s, "
            f"failover {repl_stats['repl_failover_queries_per_sec']:,.0f}"
            f" q/s ({failovers} failovers, {ok_fo}/{rn} ok), hedge "
            f"win rate {repl_stats['repl_hedge_win_rate']:.0%} at "
            f"hedge rate {repl_stats['repl_hedge_rate']:.2f}")
        shutil.rmtree(rdir, ignore_errors=True)

    # ---- reshard section: serve q/s + p99 through a LIVE worker join
    # (the elastic-membership dual-read window) vs the steady fleet.
    # A 2-worker world gains a third worker mid-load: begin opens the
    # window, catch_up adopts a shard, commit bumps the epoch — the
    # drill measures what the migration window costs the open workload.
    # BENCH_RESHARD=0 skips.
    reshard_stats = {}
    if os.environ.get("BENCH_RESHARD", "1") != "0":
        from distributed_oracle_search_tpu.data import (
            ensure_synth_dataset, read_scen,
        )
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.parallel import (
            membership as _fleet,
        )
        from distributed_oracle_search_tpu.serving import (
            EngineDispatcher, HedgeConfig, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )

        log("reshard (serve q/s through a live worker join)...")
        edir = tempfile.mkdtemp(prefix="bench-reshard-")
        epaths = ensure_synth_dataset(edir, width=24, height=18,
                                      n_queries=512, seed=37)
        econf = ClusterConfig(
            workers=["localhost"] * 2, partmethod="mod", partkey=2,
            outdir=os.path.join(edir, "index"),
            xy_file=epaths["xy"], scenfile=epaths["scen"],
            nfs=edir).validate()
        eg = Graph.from_xy(econf.xy_file)
        edc = DistributionController("mod", 2, 2, eg.n)
        for wid in range(2):
            build_worker_shard(eg, edc, wid, econf.outdir)
        write_index_manifest(econf.outdir, edc)
        equeries = read_scen(econf.scenfile)
        en = int(os.environ.get("BENCH_RESHARD_REQUESTS", 512))
        epool = equeries[np.arange(en) % len(equeries)]
        mc = _fleet.MembershipController(econf, edc, graph=eg)
        disp = EngineDispatcher(econf, graph=eg, dc=edc)
        for wid in range(2):     # warm the engines off the clock
            mine = equeries[edc.worker_of(equeries[:, 1]) == wid][:64]
            disp.answer_batch(wid, mine, RuntimeConfig(), "-")

        def _edrill(tag, during=None):
            """Closed-loop drill; ``during`` optionally runs the
            migration steps between the submit stream's halves so the
            window is genuinely live while queries flow."""
            fe = ServingFrontend(
                mc.dc_view(), disp,
                sconf=ServeConfig(max_batch=64, max_wait_ms=2.0,
                                  queue_depth=max(en, 1024),
                                  cache_bytes=0,
                                  deadline_ms=600_000.0),
                hconf=HedgeConfig(enabled=False), membership=mc)
            fe.start()
            t0 = time.perf_counter()
            submits, futs = [], []
            for i, (s, t) in enumerate(epool):
                if during is not None and i == len(epool) // 2:
                    during()
                submits.append(time.monotonic())
                futs.append(fe.submit(int(s), int(t)))
            res = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            fe.stop()
            n_ok = sum(r.ok for r in res)
            lat = [(r.t_done - ts) * 1e3
                   for r, ts in zip(res, submits) if r.ok]
            p99 = float(np.percentile(lat, 99)) if lat else float("nan")
            log(f"  {tag}: {n_ok}/{en} ok in {wall:.2f}s "
                f"({n_ok / wall:,.0f} q/s, p99 {p99:.1f} ms)")
            return n_ok, wall, p99

        ok_st, wall_st, p99_st = _edrill("steady (epoch 0)")

        def _join_now():
            mig = mc.begin(mc.plan_join("localhost"), host="localhost")
            mc.catch_up(mig)
            mc.commit(mig)

        ok_mg, wall_mg, p99_mg = _edrill("migrating (live join)",
                                         during=_join_now)
        reshard_stats = {
            "reshard_steady_queries_per_sec": round(ok_st / wall_st, 1),
            "reshard_steady_p99_ms": round(p99_st, 3),
            "reshard_migrating_queries_per_sec": round(
                ok_mg / wall_mg, 1),
            "reshard_migrating_p99_ms": round(p99_mg, 3),
            "reshard_epoch_after": int(mc.epoch),
        }
        log(f"reshard: steady "
            f"{reshard_stats['reshard_steady_queries_per_sec']:,.0f} "
            f"q/s -> migrating "
            f"{reshard_stats['reshard_migrating_queries_per_sec']:,.0f}"
            f" q/s (epoch {mc.epoch} committed, {ok_mg}/{en} ok)")
        shutil.rmtree(edir, ignore_errors=True)

    # ---- traffic section: the live congestion plane (traffic/). A zipf
    # hotspot pool served steady on the base weights, then again while a
    # rush-hour segment replay swaps diff epochs UNDER the running
    # frontend — live-swap q/s, swap-stall p99, and the scoped-vs-full
    # invalidation hit rate (how much of the warm cache survives a swap
    # because its paths provably avoid the retimed corridor).
    # BENCH_TRAFFIC=0 skips.
    traffic_stats = {}
    if os.environ.get("BENCH_TRAFFIC", "1") != "0":
        from distributed_oracle_search_tpu.data import ensure_synth_dataset
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.obs import (
            metrics as _tmetrics,
        )
        from distributed_oracle_search_tpu.serving import (
            EngineDispatcher, HedgeConfig, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.traffic import DiffEpochManager
        from distributed_oracle_search_tpu.traffic import (
            scenarios as tscen,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )

        log("traffic (live epoch swaps over a zipf hotspot pool)...")
        tdir = tempfile.mkdtemp(prefix="bench-traffic-")
        tpaths = ensure_synth_dataset(tdir, width=24, height=18,
                                      n_queries=512, seed=41)
        tconf = ClusterConfig(
            workers=["localhost"] * 2, partmethod="mod", partkey=2,
            outdir=os.path.join(tdir, "index"),
            xy_file=tpaths["xy"], scenfile=tpaths["scen"],
            nfs=tdir).validate()
        tg = Graph.from_xy(tconf.xy_file)
        tdc = DistributionController("mod", 2, 2, tg.n)
        for wid in range(2):
            build_worker_shard(tg, tdc, wid, tconf.outdir)
        write_index_manifest(tconf.outdir, tdc)
        tn = int(os.environ.get("BENCH_TRAFFIC_REQUESTS", 2048))
        tpool = tscen.zipf_queries(tg.n, tn, seed=41)
        tdisp = EngineDispatcher(tconf, graph=tg, dc=tdc)
        stream_dir = os.path.join(tdir, "stream")
        tmgr = DiffEpochManager(stream_dir, poll_ms=25.0)
        # warm every micro-batch bucket shape off the clock with the
        # serve path's own knobs (sig_k rides the program key): the
        # live burst's post-swap misses arrive in odd-sized batches,
        # and a first-swap XLA compile must not masquerade as swap
        # stall — steady-state swaps are compile-free
        twconf = RuntimeConfig(sig_k=tmgr.sig_moves)
        for wid in range(2):
            mine = tpool[tdc.worker_of(tpool[:, 1]) == wid]
            for b in (1, 2, 4, 8, 16, 32, 64):
                if len(mine) >= b:
                    tdisp.answer_batch(wid, mine[:b], twconf, "-")
        tfe = ServingFrontend(
            tdc, tdisp,
            sconf=ServeConfig(max_batch=64, max_wait_ms=2.0,
                              queue_depth=max(tn, 2048),
                              deadline_ms=600_000.0).validate(),
            hconf=HedgeConfig(enabled=False), traffic=tmgr)
        tsnap0 = _tmetrics.REGISTRY.snapshot()["counters"]
        tfe.start()

        def _tburst(pool, during=()):
            """Closed-loop burst through the LIVE frontend; ``during``
            maps submit index -> hook (segment injection points), so
            swaps land while queries flow and the post-swap misses'
            stall shows up in this burst's p99."""
            t0 = time.perf_counter()
            submits, futs = [], []
            for i, (s, t) in enumerate(pool):
                hook = during.get(i) if during else None
                if hook is not None:
                    hook()
                submits.append(time.monotonic())
                futs.append(tfe.submit(int(s), int(t)))
            res = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            lat = [(r.t_done - ts) * 1e3
                   for r, ts in zip(res, submits) if r.ok]
            return sum(r.ok for r in res), wall, lat

        try:
            _tburst(tpool)       # warm: engines compiled, cache filled
            ok_td, wall_td, lat_td = _tburst(tpool)   # steady, epoch 0
            p99_td = (float(np.percentile(lat_td, 99))
                      if lat_td else float("nan"))
            log(f"  steady (epoch 0): {ok_td}/{tn} ok "
                f"({ok_td / wall_td:,.0f} q/s, p99 {p99_td:.1f} ms)")

            # the same burst again, but rush-hour segments land at 1/3
            # and 2/3 of the stream (epoch 2 is the tent peak) and each
            # injection waits for the pump to APPLY the swap, so the
            # rest of the burst genuinely runs on the new fused diff —
            # re-keyed survivors hitting, affected entries re-answered
            trace = tscen.rush_hour_trace(tg, epochs=3, frac=0.02,
                                          peak=3.0, seed=41)

            def _inject(seg):
                def hook():
                    tscen.replay([seg], stream_dir)
                    deadline = time.monotonic() + 30.0
                    while (tfe._diff_epoch < seg["epoch"]
                           and time.monotonic() < deadline):
                        time.sleep(0.005)
                return hook

            ok_tl, wall_tl, lat_tl = _tburst(
                tpool, during={len(tpool) // 3: _inject(trace[0]),
                               (2 * len(tpool)) // 3: _inject(trace[1])})
            p99_tl = (float(np.percentile(lat_tl, 99))
                      if lat_tl else float("nan"))
            swapped = int(tfe._diff_epoch)
            log(f"  live swap: {ok_tl}/{tn} ok "
                f"({ok_tl / wall_tl:,.0f} q/s, p99 {p99_tl:.1f} ms, "
                f"{swapped} epoch(s) applied)")

            # scoped-invalidation hit rate straight from the swap
            # passes' own accounting: survivors re-keyed / entries
            # examined. (NOT a post-swap resubmission probe — the live
            # burst re-caches the hot pool under the new epoch, so a
            # probe would read near-1.0 even with scoped invalidation
            # fully broken.)
            tsnap = _tmetrics.REGISTRY.snapshot()["counters"]

            def _tdelta(name):
                return int(tsnap.get(name, 0)) - int(tsnap0.get(name, 0))

            kept = _tdelta("serve_cache_rekeyed_total")
            sdrop = _tdelta("serve_cache_invalidated_scoped_total")
            traffic_stats = {
                "traffic_steady_queries_per_sec": round(
                    ok_td / wall_td, 1),
                "traffic_steady_p99_ms": round(p99_td, 3),
                "traffic_live_swap_queries_per_sec": round(
                    ok_tl / wall_tl, 1),
                "traffic_swap_stall_p99_ms": round(p99_tl, 3),
                "traffic_epochs_swapped": swapped,
                "traffic_scoped_hit_rate": round(
                    kept / (kept + sdrop), 4) if kept + sdrop else 0.0,
                "traffic_invalidated_scoped": sdrop,
                "traffic_invalidated_full": _tdelta(
                    "serve_cache_invalidated_full_total"),
            }
            log(f"traffic: steady "
                f"{traffic_stats['traffic_steady_queries_per_sec']:,.0f}"
                f" q/s -> live-swap "
                f"{traffic_stats['traffic_live_swap_queries_per_sec']:,.0f}"
                f" q/s, scoped hit rate "
                f"{traffic_stats['traffic_scoped_hit_rate']:.0%}")
        finally:
            tfe.stop()
        shutil.rmtree(tdir, ignore_errors=True)

    control_stats = {}
    if os.environ.get("BENCH_CONTROL", "1") != "0":
        from distributed_oracle_search_tpu.control import (
            ControlConfig, ControlDaemon,
        )
        from distributed_oracle_search_tpu.data import ensure_synth_dataset
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.serving import (
            DispatchError, EngineDispatcher, HedgeConfig, ServeConfig,
            ServingFrontend,
        )
        from distributed_oracle_search_tpu.traffic import DiffEpochManager
        from distributed_oracle_search_tpu.traffic import (
            scenarios as cscen,
        )
        from distributed_oracle_search_tpu.transport.resilience import (
            BreakerRegistry,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            HealthStatus, RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )
        from distributed_oracle_search_tpu.worker.supervisor import (
            WorkerSupervisor,
        )

        log("closed-loop control (rush-hour + worker kill, policy on "
            "vs off)...")
        cdir = tempfile.mkdtemp(prefix="bench-control-")
        cpaths = ensure_synth_dataset(cdir, width=20, height=15,
                                      n_queries=256, seed=47)
        cconf = ClusterConfig(
            workers=["localhost"] * 2, partmethod="mod", partkey=2,
            outdir=os.path.join(cdir, "index"),
            xy_file=cpaths["xy"], scenfile=cpaths["scen"],
            nfs=cdir).validate()
        cg = Graph.from_xy(cconf.xy_file)
        cdc = DistributionController("mod", 2, 2, cg.n)
        for wid in range(2):
            build_worker_shard(cg, cdc, wid, cconf.outdir)
        write_index_manifest(cconf.outdir, cdc)
        cn = int(os.environ.get("BENCH_CONTROL_REQUESTS", 1200))
        crng = np.random.default_rng(47)
        cpool = cscen.zipf_queries(cg.n, cn, seed=47)
        ctrace = cscen.rush_hour_trace(cg, epochs=2, frac=0.02,
                                       peak=3.0, seed=47)

        class _ThreadProc:
            """Popen shape over an in-process worker slot, so the real
            WorkerSupervisor (and its kick/backoff machinery) can
            supervise the incident without subprocess costs."""

            _next_pid = [1]

            def __init__(self):
                self.dead = False
                self.returncode = None
                self.pid = 90_000 + self._next_pid[0]
                self._next_pid[0] += 1

            def poll(self):
                if self.dead:
                    self.returncode = 0
                    return 0
                return None

            def wait(self, timeout=None):
                if self.dead:
                    return 0
                raise subprocess.TimeoutExpired("threadproc",
                                                timeout or 0)

            def terminate(self):
                self.dead = True

            def kill(self):
                self.dead = True

        class _GatedDispatch:
            """EngineDispatcher behind a per-worker liveness gate: a
            dead worker's sends hang (the dead-FIFO analog) until a
            send-timeout, so the un-policed fleet pays the realistic
            price for routing at a corpse."""

            def __init__(self, inner, alive, hang_s=0.6):
                self.inner = inner
                self.alive = alive
                self.hang_s = hang_s

            def answer_batch(self, wid, q, rconf, diff, via=None):
                w = wid if via is None else via
                if not self.alive.get(w, True):
                    deadline = time.monotonic() + self.hang_s
                    while not self.alive.get(w, True):
                        if time.monotonic() >= deadline:
                            raise DispatchError(
                                f"worker {w} unreachable")
                        time.sleep(0.01)
                return self.inner.answer_batch(wid, q, rconf, diff,
                                               via=via)

        def _control_run(policy_on):
            alive = {0: True, 1: True}
            procs = {}

            def spawn(w):
                alive[w.wid] = True
                procs[w.wid] = _ThreadProc()
                return procs[w.wid]

            def probe(w):
                if alive.get(w.wid) and not w.proc.dead:
                    return HealthStatus(ok=True, wid=w.wid)
                return None

            sup = WorkerSupervisor(cconf, conf_path=None,
                                   spawn_fn=spawn, probe_fn=probe,
                                   ping_interval_s=0.1,
                                   backoff_base_s=6.0,
                                   backoff_cap_s=8.0)
            reg = BreakerRegistry(threshold=3, cooldown_s=1.0,
                                  enabled=True)
            stream = os.path.join(
                cdir, f"stream-{'on' if policy_on else 'off'}")
            cmgr = DiffEpochManager(stream, poll_ms=25.0)
            cdisp = _GatedDispatch(
                EngineDispatcher(cconf, graph=cg, dc=cdc), alive)
            fe = ServingFrontend(
                cdc, cdisp,
                sconf=ServeConfig(max_batch=32, max_wait_ms=2.0,
                                  queue_depth=max(cn, 2048),
                                  deadline_ms=2000.0).validate(),
                hconf=HedgeConfig(enabled=False), traffic=cmgr,
                registry=reg, breaker_key=lambda wid: wid)
            daemon = None
            if policy_on:
                daemon = ControlDaemon(
                    ControlConfig(enabled=True, interval_s=0.1,
                                  cooldown_s=0.5, hold_ticks=1,
                                  clean_probes=1, unhealthy_pings=2),
                    supervisor=sup, registry=reg, frontend=fe,
                    breaker_key=lambda wid: wid,
                    replicate_fn=lambda shard: None,
                    probe_fn=lambda wid: bool(alive.get(wid)))
            sup.start(wait_ready_s=10)
            fe.start()
            if daemon is not None:
                daemon.start()
            kill_at = cn // 3
            shift_at = (2 * cn) // 3
            t_kill = None
            try:
                # warm: engines compiled, shapes resident
                for f in [fe.submit(int(s), int(t))
                          for s, t in cpool[:64]]:
                    f.result(60)
                submits, futs = [], []
                for i, (s, t) in enumerate(cpool):
                    if i == kill_at:
                        # the incident: worker 1 dies mid-serve
                        t_kill = time.monotonic()
                        procs[1].dead = True
                        alive[1] = False
                    if i == shift_at:
                        # the hotspot shift: a rush-hour segment lands
                        # and the pump swaps the fused diff live
                        cscen.replay([ctrace[0]], stream)
                    submits.append(time.monotonic())
                    futs.append(fe.submit(int(s), int(t)))
                    time.sleep(0.003)
                res = [f.result(60) for f in futs]
                t_end = time.monotonic()
            finally:
                if daemon is not None:
                    daemon.stop()
                fe.stop()
                sup.stop()
                reg.shutdown()
            ok = [(r, ts) for r, ts in zip(res, submits) if r.ok]
            shed_rate = 1.0 - len(ok) / len(res)
            lat = [(r.t_done - ts) * 1e3 for r, ts in ok]
            p99 = float(np.percentile(lat, 99)) if lat else float("nan")
            # recovery: first OK non-cached answer to a query SUBMITTED
            # after the kill and routed to the killed worker's shard —
            # in-flight stragglers and cache hits don't prove the
            # worker came back
            healed = [r.t_done for r, ts in ok
                      if t_kill is not None and ts > t_kill
                      and not r.cached
                      and int(cdc.worker_of(np.asarray([r.t]))[0]) == 1]
            # no healed sample within the burst → report the observed
            # outage as a floor (the fleet never recovered on camera)
            recover = (min(healed) - t_kill) if healed \
                else (t_end - t_kill if t_kill else 0.0)
            return shed_rate, recover, p99

        shed_off, rec_off, p99_off = _control_run(policy_on=False)
        log(f"  policy OFF: shed {shed_off:.1%}, recover "
            f"{rec_off:.2f}s, p99 {p99_off:.1f} ms")
        shed_on, rec_on, p99_on = _control_run(policy_on=True)
        log(f"  policy ON:  shed {shed_on:.1%}, recover "
            f"{rec_on:.2f}s, p99 {p99_on:.1f} ms")
        control_stats = {
            "control_shed_rate": round(shed_on, 4),
            "control_recover_seconds": round(rec_on, 3),
            "control_p99_ms": round(p99_on, 3),
            "control_off_shed_rate": round(shed_off, 4),
            "control_off_recover_seconds": round(rec_off, 3),
            "control_off_p99_ms": round(p99_off, 3),
        }
        shutil.rmtree(cdir, ignore_errors=True)

    integrity_stats = {}
    if os.environ.get("BENCH_INTEGRITY", "1") != "0":
        from distributed_oracle_search_tpu.data import ensure_synth_dataset
        from distributed_oracle_search_tpu.data.graph import Graph
        from distributed_oracle_search_tpu.integrity.audit import (
            AnswerAuditor, make_reference_fn,
        )
        from distributed_oracle_search_tpu.integrity.scrub import (
            TableScrubber,
        )
        from distributed_oracle_search_tpu.models.cpd import (
            build_worker_shard, write_index_manifest,
        )
        from distributed_oracle_search_tpu.parallel.partition import (
            DistributionController,
        )
        from distributed_oracle_search_tpu.serving import (
            EngineDispatcher, HedgeConfig, ServeConfig, ServingFrontend,
        )
        from distributed_oracle_search_tpu.testing import faults
        from distributed_oracle_search_tpu.traffic import (
            scenarios as iscen,
        )
        from distributed_oracle_search_tpu.transport.wire import (
            RuntimeConfig,
        )
        from distributed_oracle_search_tpu.utils.config import (
            ClusterConfig,
        )

        log("answer integrity (audit overhead at 0/1/10 per mille, "
            "scrub overhead, corrupt-resident + corrupt-answer "
            "drills)...")
        igdir = tempfile.mkdtemp(prefix="bench-integrity-")
        igpaths = ensure_synth_dataset(igdir, width=20, height=15,
                                       n_queries=256, seed=53)
        igconf = ClusterConfig(
            workers=["localhost"] * 2, partmethod="mod", partkey=2,
            outdir=os.path.join(igdir, "index"),
            xy_file=igpaths["xy"], scenfile=igpaths["scen"],
            nfs=igdir).validate()
        ig_g = Graph.from_xy(igconf.xy_file)
        ig_dc = DistributionController("mod", 2, 2, ig_g.n)
        for wid in range(2):
            build_worker_shard(ig_g, ig_dc, wid, igconf.outdir)
        write_index_manifest(igconf.outdir, ig_dc)
        ig_n = int(os.environ.get("BENCH_INTEGRITY_REQUESTS", 2000))
        ig_pool = iscen.zipf_queries(ig_g.n, ig_n, seed=53)

        def _integrity_run(audit_pm=0, scrub=False, answer_fp=False,
                           pool=None):
            """One timed serving burst; the cache is off so every
            request pays a real dispatch (an audit/scrub overhead
            hidden behind cache hits would be a meaningless number).
            Returns (q/s, ok results, audit divergence count)."""
            pool = ig_pool if pool is None else pool
            igdisp = EngineDispatcher(igconf, graph=ig_g, dc=ig_dc)
            igfe = ServingFrontend(
                ig_dc, igdisp,
                sconf=ServeConfig(max_batch=32, max_wait_ms=2.0,
                                  queue_depth=max(ig_n, 2048),
                                  deadline_ms=5000.0,
                                  cache_bytes=0).validate(),
                rconf=RuntimeConfig(answer_fp=answer_fp),
                hconf=HedgeConfig(enabled=False))
            auditor = scrubber = None
            if audit_pm:
                auditor = AnswerAuditor(
                    igdisp, audit_pm,
                    reference_fn=make_reference_fn(ig_g),
                    queue_max=1024)
                igfe.auditor = auditor
            igfe.start()
            try:
                # warm outside the timed window: engines built,
                # programs compiled
                for f in [igfe.submit(int(s), int(t))
                          for s, t in pool[:64]]:
                    f.result(60)
                if scrub:
                    scrubber = TableScrubber(
                        lambda: list(igdisp._engines.values()), 0.05)
                    scrubber.start()
                t0 = time.monotonic()
                futs = [igfe.submit(int(s), int(t)) for s, t in pool]
                res = [f.result(60) for f in futs]
                wall = time.monotonic() - t0
                divergence = 0
                if auditor is not None:
                    # drain the audit queue so divergences booked
                    # off-path are all counted
                    end = time.monotonic() + 60
                    while (not auditor._q.empty()
                           and time.monotonic() < end):
                        time.sleep(0.02)
                    divergence = sum(auditor.snapshot().values())
            finally:
                if scrubber is not None:
                    scrubber.stop()
                if auditor is not None:
                    auditor.stop()
                igfe.stop()
            ok = [r for r in res if r.ok]
            return len(ok) / wall, ok, divergence

        base_qps, base_ok, _ = _integrity_run()
        truth = {(r.s, r.t): (int(r.cost), int(r.plen))
                 for r in base_ok}
        audit1_qps, _, _ = _integrity_run(audit_pm=1)
        audit10_qps, _, _ = _integrity_run(audit_pm=10)
        scrub_qps, _, _ = _integrity_run(scrub=True)
        # clean-run audit at full rate: every batch re-executed on the
        # CPU reference lane — ANY divergence here is a real bug
        _, _, clean_div = _integrity_run(audit_pm=1000,
                                         pool=ig_pool[:400])

        # corrupt-answer drill: bits flip in reply payloads after the
        # fingerprint is computed; the dispatcher's verifier must
        # suppress every one — served answers stay truth-identical
        os.environ["DOS_FAULTS"] = "corrupt-answer;times=20"
        faults.reset()
        try:
            _, drill_ok, _ = _integrity_run(answer_fp=True)
        finally:
            del os.environ["DOS_FAULTS"]
            faults.reset()
        wrong = sum(1 for r in drill_ok
                    if (r.s, r.t) in truth
                    and truth[(r.s, r.t)] != (int(r.cost),
                                              int(r.plen)))

        # corrupt-resident drill: flip rows in one engine's RESIDENT
        # table behind serving's back; detection latency is flip ->
        # the scrubber's corrupt-block booking (+ rebind from disk)
        igdisp = EngineDispatcher(igconf, graph=ig_g, dc=ig_dc)
        igfe = ServingFrontend(
            ig_dc, igdisp,
            sconf=ServeConfig(max_batch=32, max_wait_ms=2.0,
                              queue_depth=2048, deadline_ms=5000.0,
                              cache_bytes=0).validate(),
            hconf=HedgeConfig(enabled=False))
        igfe.start()
        detect_s = float("nan")
        try:
            for f in [igfe.submit(int(s), int(t))
                      for s, t in ig_pool[:64]]:
                f.result(60)
            ig_eng = next(iter(igdisp._engines.values()))
            bad = np.array(np.asarray(ig_eng.fm), np.int8, copy=True)
            bad[0, :] = np.where(bad[0, :] <= 0, 1, 0)
            ig_eng.fm = bad
            igscrub = TableScrubber(
                lambda: list(igdisp._engines.values()), 0.05)
            t_flip = time.monotonic()
            igscrub.start()
            try:
                while time.monotonic() - t_flip < 30:
                    if igscrub.corrupt_blocks > 0:
                        detect_s = time.monotonic() - t_flip
                        break
                    time.sleep(0.01)
            finally:
                igscrub.stop()
        finally:
            igfe.stop()

        integrity_stats = {
            "integrity_base_queries_per_sec": round(base_qps, 1),
            "integrity_audit1_queries_per_sec": round(audit1_qps, 1),
            "integrity_audit10_queries_per_sec": round(audit10_qps, 1),
            "integrity_scrub_queries_per_sec": round(scrub_qps, 1),
            "integrity_audit_overhead_frac": round(
                1.0 - audit1_qps / base_qps, 4),
            "integrity_scrub_overhead_frac": round(
                1.0 - scrub_qps / base_qps, 4),
            "integrity_audit_divergence": int(clean_div),
            "integrity_wrong_answers_served": int(wrong),
            "integrity_detect_seconds": round(detect_s, 3),
        }
        log(f"  base {base_qps:,.0f} q/s; audit 1 per mille "
            f"{audit1_qps:,.0f} q/s "
            f"({integrity_stats['integrity_audit_overhead_frac']:+.1%}"
            f" overhead); scrub on {scrub_qps:,.0f} q/s; clean-run "
            f"divergences {clean_div}; corrupted answers served "
            f"{wrong}; resident corruption detected in "
            f"{detect_s:.2f}s")
        shutil.rmtree(igdir, ignore_errors=True)

    target_time = 1.0  # north star: whole scenario < 1 s (BASELINE.json)
    detail = {
        "graph_nodes": g.n,
        "graph_edges": g.m,
        "n_queries": n_queries,
        "scenario_seconds": round(t_scen.interval, 4),
        "warmup_seconds": warmups,
        "diff_queries_per_sec": round(n_queries / t_diff.interval, 1),
        "dist_queries_per_sec": round(n_queries / t_dist.interval, 1),
        **cpu_stats,
        **table_stats,
        "cpd_build_seconds": round(t_build_s, 2),
        "cpd_rows_per_sec": round(rows_per_s, 1),
        **verify_stats,
        "roofline": {
            "kernel_seconds": round(t_kern_s, 4),
            "peak_gather_meps": round(peak_gather / 1e6, 1),
            "walk_useful_gather_meps": round(achieved_gather / 1e6, 1),
            "walk_issued_gather_meps": round(issued_gather / 1e6, 1),
            # issued/peak: how close the bucketed walk's issue rate
            # comes to a full-width dependent-gather chain. The
            # bucket tuning trades THIS DOWN for fewer wasted lanes
            # (each bucket exits at its own max length), so read it
            # WITH issue_efficiency (useful/issued, the waste
            # metric) — narrower buckets raise efficiency and total
            # speed while lowering raw issue rate
            "walk_gather_utilization": round(
                issued_gather / peak_gather, 3),
            "walk_issue_efficiency": round(
                achieved_gather / issued_gather, 3),
            # non-pad lanes / issued lanes: the padding-proof figure
            # for kernel-vs-kernel roofline comparisons (see the
            # honest-lane-accounting note at its computation)
            "walk_useful_lane_fraction": round(useful_lane_fraction, 3),
            "hbm_stream_gbps": round(hbm_bw / 1e9, 1),
            # XLA cost/memory analysis of the walk program + the derived
            # achieved-vs-peak gather-bandwidth figure (obs.device)
            **({"walk_flops": walk_costs.get("flops"),
                "walk_bytes_accessed": walk_costs.get("bytes_accessed"),
                "walk_hbm_bytes": walk_costs.get("hbm_bytes"),
                "walk_achieved_gbps": walk_costs.get("achieved_gbps"),
                "walk_hbm_bw_utilization":
                    walk_costs.get("hbm_bw_utilization")}
               if walk_costs else {}),
        },
        **scale_stats,
        **road_stats,
        **comp_stats,
        **delta_stats,
        **weak_stats,
        **mesh_stats,
        **multichip_stats,
        **serve_stats,
        **rpc_stats,
        **gateway_stats,
        **gateway_ha_stats,
        **telemetry_stats,
        **repl_stats,
        **reshard_stats,
        **traffic_stats,
        **control_stats,
        **integrity_stats,
        "devices": len(devices),
        "platform": devices[0].platform,
    }
    # structured internals: the obs registry's counters + per-phase
    # histograms accumulated by whatever instrumented paths this run
    # exercised (detail file only — the stdout line stays compact)
    from distributed_oracle_search_tpu.obs import metrics as obs_metrics
    detail["obs"] = obs_metrics.REGISTRY.snapshot()
    # per-program-key XLA cost/memory analyses accumulated by every
    # engine this run compiled programs in (obs.device): FLOPs, bytes
    # accessed, HBM footprint per (alg, shape, knobs) key
    detail["device_costs"] = obs_device.snapshot()
    payload = {
        "metric": "scenario_queries_per_sec",
        "value": round(qps, 1),
        "unit": "queries/s",
        "vs_baseline": round(target_time / t_scen.interval, 3),
        "detail": detail,
    }
    # full per-section detail: to a sidecar file + stderr. The driver of
    # record keeps only the LAST ~2000 stdout chars and parses the final
    # line — r04's fat single line overflowed that window and the record
    # came back unparseable (BENCH_r04.json "parsed": null)
    here = os.path.dirname(os.path.abspath(__file__))
    detail_path = os.path.join(here, "BENCH_DETAIL.json")
    with open(detail_path, "w") as f:
        json.dump(payload, f, indent=1)
    log("full detail -> " + detail_path)
    log("full detail: " + json.dumps(payload))

    headline_keys = (
        "tpu_build_parity_cores", "tpu_query_speedup",
        "tpu_dist_bulk_speedup", "table_prepare_seconds",
        "table_multi_amortization", "tpu_astar_queries_per_sec",
        "scale_build_rows_per_sec", "scale_build_parity_cores",
        "scale_stream_queries_per_sec", "scale_stream_wire_mb",
        "scale_stream_mb", "scale_stream_warm_queries_per_sec",
        "scale_tpu_stream_speedup", "scale_tpu_resident_speedup",
        "road_build_parity_cores", "road_tpu_build_rows_per_sec",
        "road_stream_queries_per_sec", "road_resident_queries_per_sec",
        "road_tpu_resident_speedup", "road_multidiff_fused_speedup",
        "cpd_resident_bytes_ratio", "compressed_walk_queries_per_sec",
        "compressed_vs_raw_walk_ratio",
        "build_delta_vs_full_ratio", "build_delta_rows_per_sec",
        "shard_strong_scaling_rows_per_sec",
        "shard_strong_scaling_rows_per_sec_w1",
        "shard_strong_scaling_rows_per_sec_w8",
        "shard_strong_scaling_overhead_w8_seconds",
        "mesh_build_rows_per_sec_d8", "mesh_walk_queries_per_sec_d8",
        "mesh_mat_rows_per_sec_d8", "multichip_smoke_ok",
        "serve_queries_per_sec", "serve_p99_ms",
        "serve_cache_hit_rate", "serve_mean_batch_fill",
        "serve_rpc_vs_fifo_dispatch_ratio", "serve_rpc_dispatch_ms",
        "serve_fifo_dispatch_ms", "serve_rpc_p99_ms",
        "serve_fifo_p99_ms",
        "telemetry_publish_p99_ms", "telemetry_publish_overhead_frac",
        "telemetry_head_ingest_per_sec",
        "traffic_live_swap_queries_per_sec", "traffic_swap_stall_p99_ms",
        "traffic_scoped_hit_rate",
        "control_shed_rate", "control_off_shed_rate",
        "control_recover_seconds", "control_off_recover_seconds",
        "integrity_audit_overhead_frac",
        "integrity_wrong_answers_served", "integrity_detect_seconds",
        "devices", "platform",
    )
    headline = {k: detail[k] for k in headline_keys if k in detail}
    headline["walk_gather_utilization"] = \
        detail["roofline"]["walk_gather_utilization"]
    headline["walk_issue_efficiency"] = \
        detail["roofline"]["walk_issue_efficiency"]
    headline["walk_useful_lane_fraction"] = \
        detail["roofline"]["walk_useful_lane_fraction"]
    line = json.dumps({
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload["unit"],
        "vs_baseline": payload["vs_baseline"],
        "detail_file": "BENCH_DETAIL.json",
        "headline": headline,
    })
    # hard gate on the driver's tail window (~2000 chars): a line that
    # outgrows it silently destroys the round's number of record
    assert len(line) < 1800, f"final bench line too long: {len(line)}"
    print(line)


if __name__ == "__main__":
    main()
