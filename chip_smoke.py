#!/usr/bin/env python3
"""Bring-up smoke: serve a real CPD shard on the chip through the
system's own entry points, and check every answer against the CPU
reference oracle.

Default (one chip): generate the 320x320 synthetic city (102,400
nodes) with its congestion diff, build worker 0's shard of a
``mod``-partitioned 8-worker fleet with ``worker.build`` (12,800
target rows, 1.31 GB of int8 table), serve it with ``cli.gateway
--backend inproc`` under the diff, and send 2,048 pair queries (64
targets owned by worker 0, 32 sources each) through ``DosClient``.

``--chips 4``: the sharded ``partmethod: "tpu"`` path instead. One
``cli.make_cpds`` process builds all 102,400 rows over a 4-device mesh
(25,600 rows per chip), then ``cli.process_query`` answers a campaign
of 2,048 pairs (16 sampled targets per shard, 32 sources each).

Only the children touch JAX, one after another; this process never
does, so each child can own the chip. Every phase prints one JSON
line. The last line is ``{"ok": ..., "device": {...}}`` with the device
as the chip-holding child reported it. Any platform other than ``tpu``
is refused: before the phases by default, after them with
``--rehearse``, so a CPU rehearsal of a cut city (``JAX_PLATFORMS=cpu
--rehearse --width 24 --height 18``) runs everything and still exits
nonzero.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

from distributed_oracle_search_tpu.data.formats import read_diff, write_scen
from distributed_oracle_search_tpu.data.graph import Graph
from distributed_oracle_search_tpu.data.synth import ensure_synth_dataset
from distributed_oracle_search_tpu.gateway.client import DosClient
from distributed_oracle_search_tpu.models.reference import (
    first_move_matrix, table_search_walk,
)
from distributed_oracle_search_tpu.parallel.partition import (
    DistributionController,
)
from distributed_oracle_search_tpu.utils.compile_cache import cache_dir
from distributed_oracle_search_tpu.utils.config import ClusterConfig

ROOT = os.path.dirname(os.path.abspath(__file__))
#: the city's side: 320 x 320 = 102,400 nodes
SIDE = 320
#: the 1-chip fleet: worker 0 of 8, mod-partitioned
FLEET = 8
#: per phase: targets x sources = 64 x 32 = 2,048 pair queries
N_TARGETS, N_SOURCES = 64, 32
#: pairs per client frame: half the gateway's default per-shard queue
FRAME = 128
#: build rows per kernel call (the device's live distance rows)
BUILD_CHUNK = 1024
#: the whole run must end inside the driver's 1,200 s
BUDGET_S = 1100.0


class PhaseFailed(RuntimeError):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Children:
    """The chip-holding child processes, run strictly one at a time;
    every one is stopped on the way out."""

    def __init__(self, workdir: str, deadline: float):
        self.workdir = workdir
        self.deadline = deadline
        self.procs: list[subprocess.Popen] = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)

    def left(self) -> float:
        return max(self.deadline - time.monotonic(), 1.0)

    def start(self, name: str, *argv: str):
        """``python *argv`` (a ``-m`` module or ``-c`` code)."""
        if any(p.poll() is None for p in self.procs):
            raise PhaseFailed(f"{name}: another child still holds the "
                              "device")
        log = open(os.path.join(self.workdir, f"{name}.log"), "wb")
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT,
            env=self.env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
        log.close()
        proc.smoke_name = name
        self.procs.append(proc)
        return proc

    def wait(self, proc, timeout: float | None = None) -> int:
        try:
            rc = proc.wait(timeout=min(timeout or self.left(),
                                       self.left()))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{proc.smoke_name}: still running at its "
                              "time limit") from None
        if rc != 0:
            raise PhaseFailed(f"{proc.smoke_name}: exit code {rc}; "
                              f"last log lines:\n{self.tail(proc)}")
        return rc

    def tail(self, proc, lines: int = 30) -> str:
        path = os.path.join(self.workdir, f"{proc.smoke_name}.log")
        with open(path, "rb") as f:
            return "\n".join(f.read().decode(errors="replace")
                             .splitlines()[-lines:])

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGTERM)
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    os.killpg(proc.pid, signal.SIGKILL)
                    proc.wait()


def make_city(workdir: str, width: int, height: int, seed: int):
    t0 = time.perf_counter()
    paths = ensure_synth_dataset(os.path.join(workdir, "data"),
                                 width=width, height=height, seed=seed)
    g = Graph.from_xy(paths["xy"])
    emit("data", nodes=int(g.n), edges=int(len(g.w)),
         diff_edges=int(len(read_diff(paths["diff"])[0])),
         seconds=time.perf_counter() - t0)
    return g, paths


def sample_pairs(rng, g: Graph, owned_by_shard: list) -> tuple:
    """Targets drawn evenly from each listed shard's owned rows, with
    ``N_SOURCES`` random sources each."""
    per = max(N_TARGETS // len(owned_by_shard), 1)
    targets = np.concatenate([
        rng.choice(owned, size=min(per, len(owned)), replace=False)
        for owned in owned_by_shard]).astype(np.int64)
    sources = rng.integers(0, g.n, size=(len(targets), N_SOURCES))
    pairs = np.stack([sources.reshape(-1),
                      np.repeat(targets, N_SOURCES)], axis=1)
    return targets, pairs


def cpu_oracle(g: Graph, w_query, targets, pairs) -> np.ndarray:
    """``(cost, plen, finished)`` per pair: the free-flow first-move
    rows of the targets, walked on the diffed weights."""
    t0 = time.perf_counter()
    fm = first_move_matrix(g, targets)
    row = {int(t): i for i, t in enumerate(targets)}
    out = np.array([table_search_walk(
        g, lambda x, t: fm[row[t], x], int(s), int(t), w_query=w_query)[:3]
        for s, t in pairs], np.int64)
    emit("oracle", targets=int(len(targets)), queries=int(len(pairs)),
         seconds=time.perf_counter() - t0)
    return out


def compare(want: np.ndarray, got: np.ndarray, failed: int) -> None:
    matched = int((want == got).all(axis=1).sum())
    emit("check", sent=int(len(want)), matched=matched, failed=failed,
         finished=int(got[:, 2].sum()))
    if failed or matched != len(want) or not got[:, 2].all():
        raise PhaseFailed(f"{len(want) - matched} of {len(want)} answers "
                          f"differ from the CPU oracle, {failed} failed, "
                          f"{int(len(want) - got[:, 2].sum())} unfinished")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def one_chip(args, kids: Children, rng) -> dict:
    g, paths = make_city(args.workdir, args.width, args.height, args.seed)
    index = os.path.join(args.workdir, "index")
    conf_path = os.path.join(args.workdir, "cluster-conf.json")
    ClusterConfig(
        workers=["localhost"] * FLEET, partmethod="mod", partkey=FLEET,
        outdir=index, xy_file=paths["xy"], scenfile=paths["scen"],
        diffs=[paths["diff"]], nfs=args.workdir,
    ).validate().save(conf_path)
    dc = DistributionController("mod", FLEET, FLEET, g.n)
    rows = int(dc.n_owned(0))
    targets, pairs = sample_pairs(rng, g, [dc.owned(0)])

    build_dump = os.path.join(args.workdir, "build-metrics.json")
    t0 = time.perf_counter()
    build = kids.start(
        "build", "-m", "distributed_oracle_search_tpu.worker.build",
        "--input", paths["xy"], "--partmethod", "mod",
        "--partkey", str(FLEET), "--workerid", "0",
        "--maxworker", str(FLEET), "--outdir", index,
        "--chunk", str(BUILD_CHUNK), "--metrics-dump", build_dump)
    # the CPU oracle runs here while the child builds on the device
    want = cpu_oracle(g, g.weights_with_diff(paths["diff"]), targets,
                      pairs)
    kids.wait(build)
    build_s = time.perf_counter() - t0
    emit("build", rows=rows, nodes=int(g.n), table_bytes=rows * int(g.n),
         seconds=build_s, rows_per_sec=rows / build_s)

    sock_dir = os.path.join(args.workdir, "sock")
    os.makedirs(sock_dir, exist_ok=True)
    sock = os.path.join(sock_dir, "dos-gateway-f0.sock")
    port = free_port()
    gw_dump = os.path.join(args.workdir, "gateway-metrics.json")
    # the gateway as users start it: default queue, batch and deadlines.
    # It loads the shard and compiles its walk before it listens.
    t0 = time.perf_counter()
    gateway = kids.start(
        "gateway", "-m", "distributed_oracle_search_tpu.cli.gateway",
        "-c", conf_path, "--backend", "inproc", "--diff", paths["diff"],
        "--replicas", "1", "--socket-dir", sock_dir,
        "--obs-port", str(port), "--metrics-dump", gw_dump)
    while not os.path.exists(sock):
        if gateway.poll() is not None:
            raise PhaseFailed(f"gateway exited rc={gateway.returncode} "
                              f"before listening:\n{kids.tail(gateway)}")
        if kids.left() <= 1.0:
            raise PhaseFailed("gateway never listened")
        time.sleep(0.2)
    ready_s = time.perf_counter() - t0

    client = DosClient(sock)
    got = np.zeros((len(pairs), 3), np.int64)
    statuses: dict[str, int] = {}
    times = []
    try:
        for i in range(0, len(pairs), FRAME):
            tb = time.perf_counter()
            rows_ = client.query_batch(
                [tuple(map(int, p)) for p in pairs[i:i + FRAME]])
            times.append(time.perf_counter() - tb)
            for j, (status, cost, plen, fin, _cached) in enumerate(rows_):
                statuses[status] = statuses.get(status, 0) + 1
                got[i + j] = (cost, plen, fin)
    finally:
        client.close()
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/statusz",
                                timeout=30) as r:
        device = json.load(r)["device"]
    if "error" in device:
        raise PhaseFailed(f"gateway device report: {device['error']}")
    os.killpg(gateway.pid, signal.SIGTERM)
    kids.wait(gateway, timeout=120)
    snap = read_json(gw_dump)
    counters = snap["counters"]
    jit = snap["histograms"].get("worker_jit_compile_seconds", {})
    emit("serve", ready_seconds=ready_s, frames=len(times),
         frame_pairs=FRAME, first_frame_seconds=times[0],
         max_later_frame_seconds=max(times[1:], default=0.0),
         first_compile_seconds=jit.get("sum", 0.0),
         compiles=jit.get("count", 0),
         walk_kernel=("pallas" if counters.get(
             "walk_pallas_batches_total", 0) else "xla"),
         walk_pallas_batches_total=counters.get(
             "walk_pallas_batches_total", 0),
         walk_xla_batches_total=counters.get("walk_xla_batches_total", 0),
         statuses=statuses, gateway_rc=gateway.returncode)
    emit("memory", bytes_in_use=device.get("bytes_in_use"),
         peak_bytes_in_use=device.get("peak_bytes_in_use"),
         bytes_limit=device.get("bytes_limit"))
    compare(want, got, len(pairs) - statuses.get("OK", 0))
    return {k: device[k] for k in ("platform", "kind", "count")}


def four_chips(args, kids: Children, rng) -> dict:
    g, paths = make_city(args.workdir, args.width, args.height, args.seed)
    chips = 4
    dc = DistributionController("tpu", chips, chips, g.n)
    targets, pairs = sample_pairs(
        rng, g, [dc.owned(w) for w in range(chips)])
    scen = os.path.join(args.workdir, "smoke4.scen")
    write_scen(scen, pairs, comment="chip_smoke --chips 4")
    conf_path = os.path.join(args.workdir, "cluster-conf-tpu4.json")
    ClusterConfig(
        workers=[f"tpu:{i}" for i in range(chips)], partmethod="tpu",
        partkey=chips, outdir=os.path.join(args.workdir, "index4"),
        xy_file=paths["xy"], scenfile=scen, diffs=[paths["diff"]],
        nfs=args.workdir,
    ).validate().save(conf_path)

    t0 = time.perf_counter()
    build = kids.start("make_cpds",
                       "-m", "distributed_oracle_search_tpu.cli.make_cpds",
                       "-c", conf_path, "--chunk", str(BUILD_CHUNK))
    want = cpu_oracle(g, g.weights_with_diff(paths["diff"]), targets,
                      pairs)
    kids.wait(build)
    build_s = time.perf_counter() - t0
    built = next(json.loads(line) for line in
                 reversed(kids.tail(build, 200).splitlines())
                 if line.startswith("{") and "shard_devices" in line)
    shard_devices = built["shard_devices"]
    emit("build", rows=int(g.n), rows_per_chip=int(dc.max_owned),
         table_bytes_per_chip=int(dc.max_owned) * int(g.n),
         seconds=build_s, rows_per_sec=g.n / build_s,
         shard_devices=shard_devices)
    if len(set(shard_devices.values())) != chips:
        raise PhaseFailed(f"{chips} shards on devices {shard_devices}: "
                          f"want {chips} distinct")

    out = os.path.join(args.workdir, "campaign4")
    t0 = time.perf_counter()
    campaign = kids.start(
        "process_query",
        "-m", "distributed_oracle_search_tpu.cli.process_query",
        "-c", conf_path, "-o", out)
    kids.wait(campaign)
    ans = np.load(os.path.join(out, "answers.npz"))
    got = np.stack([ans["cost"][0], ans["plen"][0], ans["finished"][0]],
                   axis=1).astype(np.int64)
    metrics = read_json(os.path.join(out, "metrics.json"))
    emit("campaign", queries=int(len(pairs)),
         seconds=time.perf_counter() - t0,
         t_process=metrics.get("t_process"),
         failed_batches=len(metrics.get("failed_batches", [])))
    compare(want, got, len(metrics.get("failed_batches", [])))
    return built["device"]


def probe_device(kids: Children) -> dict:
    """The device JAX finds, from a short child that then exits."""
    probe = kids.start("probe", "-c", (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"))
    kids.wait(probe, timeout=300)
    return json.loads(kids.tail(probe, 1))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--width", type=int, default=SIDE)
    p.add_argument("--height", type=int, default=SIDE)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=os.path.join(ROOT, ".chip_smoke"))
    p.add_argument("--rehearse", action="store_true",
                   help="off a TPU, run the phases before refusing the "
                        "platform (use with a cut --width/--height)")
    args = p.parse_args(argv)
    args.workdir = os.path.abspath(args.workdir)
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    emit("setup", chips=args.chips, workdir=args.workdir,
         compile_cache=cache_dir(), width=args.width, height=args.height,
         seed=args.seed)
    kids = Children(args.workdir, time.monotonic() + BUDGET_S)
    rng = np.random.default_rng(args.seed)
    device = None
    try:
        found = probe_device(kids)
        emit("platform", **found)
        if found["platform"] != "tpu" and not args.rehearse:
            # off the chip only a rehearsal runs the phases: the full
            # size would build and hold a 1.31 GB shard on a host CPU
            device = found
            raise PhaseFailed(f"platform {found['platform']!r} is not a "
                              "TPU (--rehearse runs the phases anyway)")
        run = four_chips if args.chips == 4 else one_chip
        device = run(args, kids, rng)
        ok = device["platform"] == "tpu"
        if not ok:
            emit("failed", error=f"platform {device['platform']!r} is "
                 "not a TPU: no chip result")
    except Exception as e:  # noqa: BLE001 -- every failure ends in the
        # one result line below, with its reason
        emit("failed", error=f"{type(e).__name__}: {e}")
        for proc in kids.procs:
            print(f"--- {proc.smoke_name} log tail:\n{kids.tail(proc)}",
                  file=sys.stderr)
        ok = False
    finally:
        kids.stop_all()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
