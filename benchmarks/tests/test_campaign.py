"""CPU tests of the campaign cell (``city4-campaign``): its readers, and
whole rehearsals of its run (a cut city on four CPU devices, which skip
the look for a chip) that pass, and that read ``correct`` false under
the control and under a fault that drops one chip's answers from the
answers file before the check.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_campaign.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from drivers import campaign  # noqa: E402

CELL = "city4-campaign"
#: the program's records of one run: a rehearsal of 48 parts at a
#: 192x192 city on a TPU v5e 2x2
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "campaign-progress.jsonl")
PER_LAYER = ("part_route_ms", "part_walk_ms", "part_fetch_ms", "lane_fill",
             "walk_roofline.campaign", "device_idle_pct.campaign")

# one chip's answers (worker 0's targets) left out of the answers file
DROP_CHIP = """
import numpy as np
from distributed_oracle_search_tpu.cli import process_query as pq
_run_tpu = pq.run_tpu
def run_tpu(conf, args, queries, dc, diffs):
    out = _run_tpu(conf, args, queries, dc, diffs)
    path = os.path.join(args.output, "answers.npz")
    with np.load(path) as z:
        a = {k: z[k] for k in z.files}
    keep = dc.worker_of(a["queries"][:, 1]) != 0
    np.savez(path, queries=a["queries"][keep],
             **{k: a[k][:, keep] for k in ("cost", "plen", "finished")})
    return out
pq.run_tpu = run_tpu
"""


# a program that answers but records no part (as one that ignores -s)
NO_RECORDS = """
from distributed_oracle_search_tpu.cli import process_query as pq
pq._Progress.write = lambda self, *a, **kw: None
pq._Progress.part = lambda self, *a, **kw: None
"""


def _fault_env(tmp_path, body: str) -> dict:
    (tmp_path / "sitecustomize.py").write_text(
        "import os, sys\n"
        "if os.path.basename(sys.argv[0]) == 'campaign_launcher.py':\n"
        + "".join(f"    {ln}\n" for ln in body.strip().splitlines()))
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))


def rehearse(env: dict, *extra: str) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--workload", CELL, "--seed", "3141592653", "--seconds", "3",
         "--trace", "0", "--rehearse", "--side", "24", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-3000:]
    line = out.stderr.strip().splitlines()[-1]
    return json.loads(line.split("no result: ", 1)[1])


def test_rehearsal_passes_end_to_end():
    res = rehearse(dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert {"pairs_per_s", "setup_s"} <= set(res["metrics"])
    assert res["checks"]["lost"]["value"] == 0


def test_traced_rehearsal_profiles_whole_parts():
    """The profile is placed in the window and reduced over the whole
    parts it holds (the CPU has no device planes, so the device
    readers find nothing; the span readers do)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2718281828", "--seconds", "3", "--trace", "1",
         "--rehearse", "--side", "24"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-3000:]
    lines = out.stderr.strip().splitlines()
    traced = [json.loads(ln) for ln in lines
              if ln.startswith('{"phase": "trace"')]
    assert traced and traced[0]["parts"] >= 1
    res = json.loads(lines[-1].split("no result: ", 1)[1])
    assert res["correct"] is True
    assert {"part_route_ms", "part_walk_ms", "part_fetch_ms",
            "lane_fill"} <= set(res["metrics"])
    assert res["metrics"]["lane_fill"]["value"] == pytest.approx(50.0)


def test_profile_window_holds_whole_parts():
    prof = campaign.Profile(None, "")
    parts = [{"part": k, "t0": 10.0 + k, "t1": 10.9 + k} for k in range(8)]
    assert prof.window(parts) is None
    # recorded from 12.5 to 16.95 (monotonic), wall = mono + 1000 s
    prof.span = (12.5e9 + 1e12, 16.95e9 + 1e12, 12.5, 16.95)
    (lo, hi), inside = prof.window(parts)
    assert [r["part"] for r in inside] == [3, 4, 5, 6]
    assert lo == pytest.approx(13.0e9 + 1e12)
    assert hi == pytest.approx(16.9e9 + 1e12)


def _parts(n: int, period: float) -> list:
    return [{"part": k, "t0": 10.0 + k * period,
             "t1": 10.0 + (k + 1) * period} for k in range(n)]


def test_profile_is_asked_for_with_its_lead_left():
    # a full-size window: 196 parts of 0.25 s
    prof = campaign.Profile(None, "")
    prof.plan(_parts(campaign.TRACE_AFTER_PARTS, 0.25), 196)
    assert prof.start_after is None             # too few parts timed
    prof.plan(_parts(1 + campaign.TRACE_AFTER_PARTS, 0.25), 196)
    lead = int(np.ceil(campaign.TRACE_LEAD_S / 0.25)) + campaign.TRACE_PARTS
    assert prof.start_after == 196 - lead
    # a short window is profiled at once, a too short one never
    prof = campaign.Profile(None, "")
    prof.plan(_parts(4, 0.25), 16)
    assert prof.start_after == 4
    prof = campaign.Profile(None, "")
    prof.plan(_parts(4, 0.25), 4 + campaign.TRACE_PARTS + 1)
    assert prof.start_after > 4 + campaign.TRACE_PARTS + 1


def test_control_is_not_correct():
    res = rehearse(dict(os.environ, JAX_PLATFORMS="cpu"), "--control")
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


def test_dropped_chip_is_not_correct(tmp_path):
    res = rehearse(_fault_env(tmp_path, DROP_CHIP))
    assert res["correct"] is False
    assert res["checks"]["lost"]["value"] > 0 and res["failed"] > 0


def test_no_part_records_end_the_run_with_an_error(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--workload", CELL, "--seed", "5", "--seconds", "3", "--trace",
         "0", "--rehearse", "--side", "24"],
        cwd=ROOT, env=_fault_env(tmp_path, NO_RECORDS), capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 1
    assert "recorded 0 of 16 parts" in out.stderr


# ------------------------------------------------------------- readers

def _read(name: str, run: dict):
    return harness.load_reader(name).read(run)


def _planes(walk_ns: dict, busy_ns: int):
    """TPU planes with one walk program per device, of ``walk_ns[d]``."""
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s,  # noqa: E731
                                         duration_ns=d)
    out = []
    for d, ns in walk_ns.items():
        out.append(SimpleNamespace(name=f"/device:TPU:{d}", lines=[
            SimpleNamespace(name=trace_reduce.OPS_LINE,
                            events=[ev("fusion", 0, busy_ns)]),
            SimpleNamespace(name=trace_reduce.MODULES_LINE,
                            events=[ev("jit_mesh_walk", 0, ns)])]))
    return out


def campaign_run() -> dict:
    """The run a campaign driver hands its readers, from the recorded
    records and a made-up trace of four chips."""
    with open(RECORDED) as f:
        records = [json.loads(line) for line in f]
    parts = [r for r in records if r["event"] == "part"]
    tr = trace_reduce.read_planes(
        _planes({0: 400, 1: 500, 2: 450, 3: 300}, 600), 0.0, (0.0, 1000.0))
    return {"kind": "campaign",
            "scrapes": (campaign.flat_metrics(parts[0]),
                        campaign.flat_metrics(parts[-1])),
            "trace": tr, "walk_moves_by_device": {0: 10, 1: 10, 2: 20,
                                                  3: 10},
            "peaks": {"hbm_bytes_per_s": 9e11}}


def test_readers_read_a_recorded_campaign_run():
    run = campaign_run()
    got = {m: _read(m, run) for m in PER_LAYER}
    assert all(v is not None for v in got.values()), got
    with open(RECORDED) as f:
        parts = [json.loads(ln) for ln in f if '"part"' in ln]
    m0, m1 = parts[0]["metrics"], parts[-1]["metrics"]
    want = ((m1["cpd_walk_seconds"][0] - m0["cpd_walk_seconds"][0])
            / (m1["cpd_walk_seconds"][1] - m0["cpd_walk_seconds"][1]) * 1e3)
    assert got["part_walk_ms"] == pytest.approx(want)
    assert got["lane_fill"] == pytest.approx(
        100 * (m1["cpd_pairs_total"] - m0["cpd_pairs_total"])
        / (m1["cpd_lanes_total"] - m0["cpd_lanes_total"]))
    assert 0 < got["lane_fill"] <= 100
    # the slowest chip (device 1, 500 ns) walked 10 moves: 90 bytes at
    # 900 GB/s take 0.1 ns of its 500
    assert got["walk_roofline.campaign"] == pytest.approx(0.02)
    assert got["device_idle_pct.campaign"] == pytest.approx(40.0)


def test_readers_find_nothing_in_a_serve_run():
    camp = campaign_run()
    # a serve run: its gateway exports the cpd_* series too, unmoved
    flat = {"cpd_walk_seconds_sum": 0.0, "cpd_walk_seconds_count": 0.0,
            "cpd_pairs_total": 0.0, "cpd_lanes_total": 0.0}
    run = {"kind": "serve", "scrapes": (flat, dict(flat)),
           "trace": camp["trace"], "walk_moves": 1000,
           "peaks": camp["peaks"]}
    for m in PER_LAYER:
        assert _read(m, run) is None, m
    # and a campaign run of a program without the records' metrics
    bare = dict(camp, scrapes=({}, {}), walk_moves_by_device={},
                trace=None)
    for m in PER_LAYER:
        assert _read(m, bare) is None, m


def test_scenario_lines_read_back_and_reorder(tmp_path):
    from distributed_oracle_search_tpu.data.formats import read_scen

    n, count = 147456, 5000
    mix = {"scenario": {"source": "uniform", "base_seed": 0}}
    pairs = campaign.scenario_pairs(mix, n, count)
    base = str(tmp_path / "base.scen")
    header = b"c test\n"
    with open(base, "wb") as f:
        f.write(header)
        campaign.scen_lines(pairs, n).tofile(f)
    np.testing.assert_array_equal(read_scen(base), pairs)
    perm = np.random.default_rng(1).permutation(count)
    run = str(tmp_path / "run.scen")
    campaign.write_run_scenario(base, header, n, count, perm, run)
    np.testing.assert_array_equal(read_scen(run), pairs[perm])
    assert (pairs >= 0).all() and (pairs < n).all()
    # s and t uniform: each chip owns about a quarter of the targets
    share = np.bincount(campaign.owner_of({"workers": 4}, n, pairs[:, 1]),
                        minlength=4) / count
    assert np.abs(share - 0.25).max() < 0.03
