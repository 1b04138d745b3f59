"""Campaign driver: the source's query campaign, ``cli.process_query``,
on the configuration's ``tpu`` mesh, in parts of a fixed size.

Set-up: the configuration's graph and its whole index, built once per
checkout by ``cli.make_cpds`` on the mesh; the scenario, written once
per checkout from the mix's ``base_seed`` as a ``.scen`` whose lines
each run puts in another order from ``--seed``; the run's congestion
diff. Then ``cli.process_query -c <conf> -s <part> -o <dir>`` under
``campaign_launcher.py`` loads the index, compiles every routed width
and answers the scenario part after part, each part started when the
previous part's answers are on the host (closed loop). The program
records each part as it ends (``progress.jsonl``); a program that
gives no such record within the mix's bounds ends the run with an
error. Window: every part after the first, from the first windowed
part's start to the last part's end; ``--seconds`` is not used. After
the process ends: the chips' peak memory, then the check: every pair
of the scenario answered in its place (``lost``), and a seeded sample
of each owning chip's answers with its longest walks against the plain
reference under the same diff (``mismatched``). With ``--control`` the
campaign runs on free-flow costs, which the check has to refuse.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import time

import numpy as np

import citygen
import harness
import trace_reduce
import traffic
from drivers import common
from reference import Reference, mismatches

LAUNCHER = os.path.join(harness.BENCH, "drivers", "campaign_launcher.py")
#: how often the driver looks at the program's records
POLL_S = 0.05
#: window parts timed before a traced run schedules its profile
TRACE_AFTER_PARTS = 3
#: whole parts a profile should hold. Each part walks ~750 steps of
#: many small device ops, ~290k op events per busy chip-second on the
#: chip, and the profiler takes minutes to collect and write millions:
#: a 3 s profile of the campaign took over two minutes on a v5e 2x2. So
#: the profile holds a few parts, and at most TRACE_SPAN_S
TRACE_PARTS = 3
#: seconds of parts left in the window when the profile is asked for:
#: the profiler's start takes its own time, and the parts it is to hold
#: must still come after it (asked for 1.3 s before the window's end, a
#: profile of the full-size cell on a v5e 2x2 held no whole part)
TRACE_LEAD_S = 10.0


def owner_of(partition: dict, n: int, t: np.ndarray) -> np.ndarray:
    """The worker (chip) that owns each target under ``tpu``."""
    return t // -(-n // int(partition["workers"]))


def scenario_pairs(mix: dict, n: int, count: int) -> np.ndarray:
    """The checkout's scenario in its written order: ``[count, 2]``
    int64 (s, t) from the mix's ``base_seed``."""
    spec = mix["scenario"]
    return traffic.pairs({"source": spec["source"]}, {}, np.arange(n), n,
                         count, np.random.default_rng(
                             int(spec["base_seed"])))


def line_width(n: int) -> int:
    """Bytes of one ``q <s> <t>`` line of :func:`scen_lines`."""
    return 2 * len(str(max(n - 1, 0))) + 4


def scen_lines(pairs: np.ndarray, n: int) -> np.ndarray:
    """``q <s> <t>`` lines of fixed width, zero-padded: uint8
    ``[len(pairs), line_width(n)]``, so that a run reorders them as
    rows."""
    digits = (line_width(n) - 4) // 2
    out = np.full((len(pairs), line_width(n)), ord(" "), np.uint8)
    out[:, 0], out[:, -1] = ord("q"), ord("\n")
    for col, v in ((2, pairs[:, 0].copy()), (3 + digits, pairs[:, 1].copy())):
        for j in range(digits - 1, -1, -1):
            out[:, col + j] = ord("0") + v % 10
            v //= 10
    return out


def ensure_scenario(ctx, n: int, count: int) -> tuple[str, bytes]:
    """The checkout's scenario file and its header line."""
    path = os.path.join(common.config_dir(ctx), f"scenario-{count}.scen")
    header = (f"c {ctx.cell['traffic']}: {count} pairs, base_seed "
              f"{ctx.mix['scenario']['base_seed']}\n").encode()
    if not os.path.exists(path):
        with open(path + ".tmp", "wb") as f:
            f.write(header)
            scen_lines(scenario_pairs(ctx.mix, n, count), n).tofile(f)
        os.replace(path + ".tmp", path)
    return path, header


def write_run_scenario(base: str, header: bytes, n: int, count: int,
                       perm: np.ndarray, path: str) -> None:
    """The base scenario's lines in the run's order."""
    lines = np.fromfile(base, np.uint8, offset=len(header)).reshape(
        count, line_width(n))
    with open(path, "wb") as f:
        f.write(header)
        lines[perm].tofile(f)


def write_conf(ctx, g: dict, index: str, scen: str, diffs: list,
               path: str) -> str:
    from distributed_oracle_search_tpu.utils.config import ClusterConfig

    workers = int(ctx.config["partition"]["workers"])
    ClusterConfig(workers=[f"tpu:{i}" for i in range(workers)],
                  partmethod="tpu", partkey=workers, outdir=index,
                  xy_file=g["xy"], scenfile=scen, diffs=diffs,
                  nfs=os.path.dirname(path)).validate().save(path)
    return path


def probe_program(ctx) -> None:
    """Refuse, before any build, a program that writes no per-part
    record."""
    try:
        ctx.kids.run("probe-program", [
            "-c", "from distributed_oracle_search_tpu.cli.process_query "
                  "import PROGRESS_FILE"], timeout=300)
    except harness.BenchError as e:
        raise harness.BenchError(
            "cli.process_query writes no per-part record "
            f"(no PROGRESS_FILE): {e}") from None


class Records:
    """The program's ``progress.jsonl``, read as it grows: whole lines
    only."""

    def __init__(self, path: str):
        self.path = path
        self.records: list = []
        self._buf = b""
        self._pos = 0

    def poll(self) -> int:
        """Read what was appended; returns how many records came."""
        if not os.path.exists(self.path):
            return 0
        with open(self.path, "rb") as f:
            f.seek(self._pos)
            data = f.read()
        self._pos += len(data)
        lines = (self._buf + data).split(b"\n")
        self._buf = lines.pop()
        for line in lines:
            if line.strip():
                self.records.append(json.loads(line))
        return len(lines)

    def parts(self) -> list:
        return [r for r in self.records
                if r["event"] == "part" and r["round"] == 0]

    def first(self, event: str) -> dict | None:
        return next((r for r in self.records if r["event"] == event),
                    None)


class Profile:
    """The profile of a traced run: started by SIGUSR1 to the campaign
    process and stopped by SIGUSR2 as soon as ``TRACE_PARTS`` whole
    parts have run inside it (or ``TRACE_SPAN_S`` has passed). It is
    asked for when, as the first window parts predict, ``TRACE_LEAD_S``
    of the window is left, so that the profiler's own start comes
    before the parts it holds and collecting it after the stop overlaps
    only the window's tail, not the parts whose host stages the
    window's metrics average. It is reduced over the whole parts inside
    it (:meth:`window`), so that the device time and the moves walked
    are of the same parts."""

    def __init__(self, proc, trace_dir: str):
        self.proc, self.trace_dir = proc, trace_dir
        self.start_after = None       # window parts done before SIGUSR1
        self.span = None              # (wall lo, wall hi, mono lo, mono hi)
        self.timing = {}              # the profiler's start and collection
        self._started = self._stopped = None

    def plan(self, parts: list, n_parts: int) -> None:
        """Place the profile once the first window parts are timed; a
        window with too few parts left for one gets none."""
        if self.start_after is not None or len(parts) < 1 + TRACE_AFTER_PARTS:
            return
        if n_parts - len(parts) < TRACE_PARTS + 2:
            self.start_after = n_parts + 1
            return
        period = (parts[-1]["t1"] - parts[1]["t0"]) / (len(parts) - 1)
        lead = int(np.ceil(TRACE_LEAD_S / max(period, 1e-6))) + TRACE_PARTS
        self.start_after = max(len(parts), n_parts - lead)

    def step(self, parts: list) -> None:
        now = time.monotonic()
        if "asked_at_part" not in self.timing:
            if self.start_after is not None and len(parts) >= \
                    self.start_after:
                self.timing["asked_at_part"] = len(parts)
                os.kill(self.proc.pid, signal.SIGUSR1)
                if self._wait(".started", 60):
                    self._started = (time.time_ns(), time.monotonic())
                    self.timing["start_s"] = self._started[1] - now
        elif self._started is not None and self.span is None:
            inside = [r for r in parts if r["t0"] >= self._started[1]]
            if len(inside) >= TRACE_PARTS or \
                    now - self._started[1] >= harness.TRACE_SPAN_S:
                self._stop()
        elif self.span is not None and self._stopped is None and \
                os.path.exists(os.path.join(self.trace_dir, ".stopped")):
            self._stopped = time.monotonic()
            self.timing["collect_s"] = self._stopped - self.span[3]

    def _stop(self) -> None:
        os.kill(self.proc.pid, signal.SIGUSR2)
        self.span = (self._started[0], time.time_ns(),
                     self._started[1], time.monotonic())

    def finish(self) -> None:
        """After the campaign process ended (which stops a profile
        still recording)."""
        if self._started is not None:
            if self.span is None:
                self.span = (self._started[0], time.time_ns(),
                             self._started[1], time.monotonic())
            if not self._wait(".stopped", 0):
                raise harness.BenchError("profiler never wrote .stopped")
            if self._stopped is None:
                self.timing["collect_s"] = time.monotonic() - self.span[3]

    def window(self, parts: list):
        """``((wall lo, wall hi), parts)``: the whole parts that ran
        while the profile recorded, from the first one's start to the
        last one's end in wall-clock nanoseconds; None without one."""
        if self.span is None:
            return None
        lo, _, mlo, mhi = self.span
        inside = [r for r in parts if r["t0"] >= mlo and r["t1"] <= mhi]
        if not inside:
            return None
        offset = lo - mlo * 1e9
        return ((inside[0]["t0"] * 1e9 + offset,
                 inside[-1]["t1"] * 1e9 + offset), inside)

    def _wait(self, marker: str, timeout: float) -> bool:
        """Whether ``marker`` appeared before ``timeout`` or the end of
        the campaign process."""
        path = os.path.join(self.trace_dir, marker)
        end = time.monotonic() + timeout
        while not os.path.exists(path):
            if self.proc.poll() is not None:
                return os.path.exists(path)
            if time.monotonic() > end:
                raise harness.BenchError(f"profiler never wrote {marker}")
            time.sleep(POLL_S)
        return True


def follow(ctx, proc, records: Records, device_path: str, n_parts: int,
           profile: Profile | None) -> None:
    """Watch the campaign process to its end: take its device report,
    hold it to the mix's record bounds, run the profile."""
    bounds = ctx.mix["records"]
    t_start = last = time.monotonic()
    while proc.poll() is None:
        now = time.monotonic()
        if now > ctx.kids.deadline:
            raise harness.BenchError("the campaign ran past the run's "
                                     "deadline")
        if records.poll():
            last = now
        elif not records.records and now - t_start > float(
                bounds["first_s"]):
            raise harness.BenchError(
                f"no record from the campaign within {bounds['first_s']} s "
                "of its start: the program writes no per-part record")
        elif now - last > float(bounds["gap_s"]):
            raise harness.BenchError(
                f"no record from the campaign for {bounds['gap_s']} s")
        if ctx.device is None and os.path.exists(device_path):
            ctx.accept({k: harness.read_json(device_path)[k]
                        for k in ("platform", "kind", "count")})
        if profile is not None:
            parts = records.parts()
            profile.plan(parts, n_parts)
            profile.step(parts)
        time.sleep(POLL_S)
    ctx.kids.wait(proc, timeout=60)
    records.poll()
    if profile is not None:
        profile.finish()


def flat_metrics(record: dict) -> dict:
    """A part record's metrics in ``harness.parse_prometheus``'s form."""
    out = {}
    for name, v in record.get("metrics", {}).items():
        if isinstance(v, list):
            out[f"{name}_sum"], out[f"{name}_count"] = v
        else:
            out[name] = v
    return out


def run(ctx) -> dict:
    cfg, mix, args = ctx.config, ctx.mix, ctx.args
    probe_program(ctx)
    g = common.ensure_graph(ctx)
    n = g["n"]
    part = int(mix["part_pairs"])
    count = (int(mix["rehearse_parts"]) * part if args.rehearse
             else int(mix["scenario"]["pairs"]))
    n_parts = -(-count // part)
    base, header = ensure_scenario(ctx, n, count)
    d = common.config_dir(ctx)
    index = os.path.join(d, "index")
    built = common.ensure_built(ctx, "index", [
        "-m", "distributed_oracle_search_tpu.cli.make_cpds",
        "-c", write_conf(ctx, g, index, base, ["-"],
                         os.path.join(d, "build-conf.json")),
        "--chunk", str(cfg["build"]["chunk"])], timeout=1000)
    common.emit("index", **built)
    rundir = os.path.join(harness.WORK, "runs", ctx.cell["name"])
    shutil.rmtree(rundir, ignore_errors=True)
    os.makedirs(rundir)
    eids, new_w = traffic.diff(cfg, g["w"], args.seed)
    diff_path = os.path.join(rundir, "congestion.diff")
    citygen.write_diff(diff_path, g["src"][eids], g["dst"][eids], new_w)
    w_query = g["w"].copy()
    w_query[eids] = new_w
    perm = traffic.rng_for(args.seed, 4).permutation(count)
    scen = os.path.join(rundir, "run.scen")
    write_run_scenario(base, header, n, count, perm, scen)
    pairs = scenario_pairs(mix, n, count)[perm]
    # the control: the campaign on free-flow costs
    conf = write_conf(ctx, g, index, scen,
                      ["-"] if args.control else [diff_path],
                      os.path.join(rundir, "cluster-conf.json"))
    common.emit("scenario", pairs=count, parts=n_parts,
                ready_s=time.monotonic() - ctx.t_start)
    out = os.path.join(rundir, "out")
    device_path = os.path.join(rundir, "device.json")
    trace_dir = os.path.join(rundir, "trace") if args.trace else None
    proc = ctx.kids.start("campaign", [
        LAUNCHER, trace_dir or "-", device_path, "-c", conf,
        "-s", str(part), "-o", out])
    records = Records(os.path.join(out, "progress.jsonl"))
    profile = Profile(proc, trace_dir) if trace_dir else None
    follow(ctx, proc, records, device_path, n_parts, profile)
    device = harness.read_json(device_path)
    if ctx.device is None:
        ctx.accept({k: device[k] for k in ("platform", "kind", "count")})
    return finish(ctx, g, w_query, pairs, records, out, device, profile,
                  trace_dir, n_parts)


def finish(ctx, g, w_query, pairs, records, out, device, profile,
           trace_dir, n_parts) -> dict:
    args, mix, cfg = ctx.args, ctx.mix, ctx.config
    parts = sorted(records.parts(), key=lambda r: r["part"])
    if len(parts) != n_parts or n_parts < 2:
        raise harness.BenchError(
            f"the campaign recorded {len(parts)} of {n_parts} parts")
    count, part = len(pairs), int(mix["part_pairs"])
    with np.load(os.path.join(out, "answers.npz")) as z:
        got = {k: z[k] for k in ("queries", "cost", "plen", "finished")}
    # every pair of the scenario answered, in its place
    k = min(len(got["queries"]), count)
    in_place = np.zeros(count, bool)
    in_place[:k] = (got["queries"][:k] == pairs[:k]).all(axis=1)
    lost = int((~in_place).sum())
    ans = np.zeros((count, 3), np.int64)
    ans[:k] = np.stack([got["cost"][0][:k], got["plen"][0][:k],
                        got["finished"][0][:k]], axis=1)
    owner = owner_of(cfg["partition"], g["n"], pairs[:, 1])
    t0, t1 = parts[1]["t0"], parts[-1]["t1"]
    m0, m1 = flat_metrics(parts[0]), flat_metrics(parts[-1])
    common.emit("window", parts=n_parts - 1, pairs=count - part,
                seconds=t1 - t0, lost=lost,
                compiles_in_window=(
                    m1.get("head_programs_lowered_total", 0)
                    - m0.get("head_programs_lowered_total", 0)),
                warm=records.first("warm"),
                peak_bytes=device.get("peak_bytes"))
    # the check: a seeded sample of each chip's answered pairs, with its
    # longest walks always in it, against the reference
    chk, pick = mix["check"], []
    rng = traffic.rng_for(args.seed, 3)
    for c in range(int(cfg["partition"]["workers"])):
        mine = np.nonzero(in_place & (owner == c))[0]
        pick.append(mine[np.argsort(-ans[mine, 1], kind="stable")[
            :int(chk["longest_per_chip"])]])
        pick.append(rng.choice(mine, size=min(int(chk["per_chip"]),
                                              len(mine)), replace=False))
    pick = np.unique(np.concatenate(pick)).astype(np.int64)
    t_check = time.monotonic()
    ref = Reference(g["n"], g["src"], g["dst"], g["w"], w_query)
    bad = (mismatches(ref.answers(pairs[pick])[0], ans[pick]) if len(pick)
           else 0)
    common.emit("check", compared=int(len(pick)), mismatched=bad,
                control=bool(args.control),
                longest_plen=int(ans[pick, 1].max(initial=0)),
                seconds=time.monotonic() - t_check)
    trace = None
    moves = {}
    traced = profile.window(parts) if profile is not None else None
    if traced is not None:
        wall, inside = traced
        trace = trace_reduce.load(trace_dir, wall)
        if trace is None:
            raise harness.BenchError(f"no profile under {trace_dir}")
        # each chip's moves in the parts the profile holds
        loaded = records.first("loaded") or {}
        shard_dev = {int(w): int(dev) for w, dev in
                     loaded.get("shard_devices", {}).items()}
        for r in inside:
            sl = slice(r["part"] * part, r["part"] * part + r["pairs"])
            ok = in_place[sl]
            per = np.bincount(owner[sl][ok], weights=ans[sl, 1][ok],
                              minlength=len(shard_dev))
            for w, dev in shard_dev.items():
                moves[dev] = moves.get(dev, 0.0) + float(per[w])
        common.emit("trace", parts=len(inside),
                    seconds=(wall[1] - wall[0]) / 1e9, **profile.timing)
    elif profile is not None and profile.timing:
        raise harness.BenchError(
            f"the profile held no whole part of the window: "
            f"{profile.timing}")
    run_ = {"kind": "campaign", "setup_s": t0 - ctx.t_start,
            "window_s": t1 - t0,
            "ok_count": int(in_place[part:].sum()),
            "scrapes": (m0, m1), "trace": trace,
            "walk_moves_by_device": moves, "peaks": ctx.peaks}
    peaks = [p for p in device.get("peak_bytes") or [] if p is not None]
    return {"run": run_, "attempted": count, "failed": lost,
            "checks": {"mismatched": (bad, 0), "lost": (lost, 0)},
            "memory_peak_bytes": max(peaks) if peaks else None,
            "trace": trace}
