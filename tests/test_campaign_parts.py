"""The TPU campaign in parts (``process_query -s P``): each diff round
answered in parts of P pairs, in scenario order, over one loaded index.

Answers in parts equal the one-call answers and the CPU reference oracle
(``models/reference.py``) bit for bit; every routed width is compiled
before the first part; each part is one oracle call with its
``cpd.route`` / ``cpd.walk`` / ``cpd.fetch`` spans and counters; and
``progress.jsonl`` records the stages as they happen.
"""

import json
import os

import numpy as np
import pytest

from distributed_oracle_search_tpu.cli.args import parse_args
from distributed_oracle_search_tpu.cli import process_query as pq
from distributed_oracle_search_tpu.data import (
    Graph, ensure_synth_dataset, read_diff, read_scen,
)
from distributed_oracle_search_tpu.models.reference import (
    first_move_to_target, table_search_walk,
)
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.obs import trace as obs_trace
from distributed_oracle_search_tpu.utils.config import ClusterConfig

#: the scenario's 96 pairs: 32 divides it, 25 does not (3 parts and a
#: short fourth)
N_PAIRS = 96


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("campaign"))
    paths = ensure_synth_dataset(d, width=10, height=8, n_queries=N_PAIRS,
                                 seed=21)
    return d, paths


@pytest.fixture(scope="module")
def reference(dataset):
    """Reference answers ``{diff: int64 [Q, 3]}`` of the scenario: the
    free-flow first-move walk, costed on each diff's weights."""
    _, paths = dataset
    g = Graph.from_xy(paths["xy"])
    queries = read_scen(paths["scen"])
    out = {}
    for diff in ("-", paths["diff"]):
        wq = None if diff == "-" else g.weights_with_diff(read_diff(diff))
        rows = []
        for s, t in queries:
            fm = first_move_to_target(g, int(t))
            cost, plen, fin, _ = table_search_walk(
                g, lambda x, _t: fm[x], int(s), int(t), w_query=wq)
            rows.append((cost, plen, fin))
        out[diff] = np.array(rows, np.int64)
    return out


def _conf(dataset, tmp_path, diffs):
    d, paths = dataset
    return ClusterConfig(
        workers=[f"tpu:{i}" for i in range(4)], partmethod="tpu",
        partkey=4, outdir=os.path.join(d, "index"), xy_file=paths["xy"],
        scenfile=paths["scen"],
        diffs=[paths["diff"] if x == "diff" else x for x in diffs],
    ).validate()


def _campaign(conf, out, size, extra=()):
    args = parse_args(["-o", out, "-s", str(size), *extra])
    data, stats, _ = pq.run(conf, args)
    pq.output(data, stats, args)
    with np.load(os.path.join(out, "answers.npz")) as z:
        answers = {k: z[k] for k in z.files}
    with open(os.path.join(out, pq.PROGRESS_FILE)) as f:
        records = [json.loads(line) for line in f]
    return stats, answers, records


def _rows(answers, r):
    return np.stack([answers["cost"][r], answers["plen"][r],
                     answers["finished"][r].astype(np.int64)], axis=1)


@pytest.mark.parametrize("size", [32, 25])
def test_parts_match_one_call_and_reference(dataset, reference, tmp_path,
                                            size):
    conf = _conf(dataset, tmp_path, ["diff"])
    stats1, one, rec1 = _campaign(conf, str(tmp_path / "one"), 0)
    stats_p, parted, rec_p = _campaign(conf, str(tmp_path / "parts"), size)
    queries = read_scen(conf.scenfile)
    np.testing.assert_array_equal(parted["queries"], queries)
    for k in ("cost", "plen", "finished"):
        np.testing.assert_array_equal(parted[k], one[k])
    np.testing.assert_array_equal(_rows(parted, 0),
                                  reference[conf.diffs[0]])
    # parts.csv rows: one per worker per round, summed over the parts
    for rows_1, rows_p in zip(stats1, stats_p):
        assert [r[:7] + [r[-1]] for r in rows_1] == \
            [r[:7] + [r[-1]] for r in rows_p]
    parts = [r for r in rec_p if r["event"] == "part"]
    assert [r["part"] for r in parts] == list(range(-(-N_PAIRS // size)))
    assert [r["pairs"] for r in parts] == \
        [min(size, N_PAIRS - k * size) for k in range(len(parts))]
    assert all(r["t0"] <= r["t1"] for r in parts)
    assert [r["event"] for r in rec_p[:3]] == ["start", "loaded", "warm"]


def test_fused_two_diff_rounds_in_parts(dataset, reference, tmp_path):
    conf = _conf(dataset, tmp_path, ["-", "diff"])
    _, one, _ = _campaign(conf, str(tmp_path / "one"), 0)
    _, parted, records = _campaign(conf, str(tmp_path / "parts"), 25)
    for k in ("cost", "plen", "finished"):
        np.testing.assert_array_equal(parted[k], one[k])
    for r, diff in enumerate(conf.diffs):
        np.testing.assert_array_equal(_rows(parted, r), reference[diff])
    parts = [r for r in records if r["event"] == "part"]
    # one fused walk per part answers both rounds
    assert [r["round"] for r in parts] == ["fused"] * 4


def test_size_zero_makes_one_call(dataset, tmp_path):
    conf = _conf(dataset, tmp_path, ["diff", "-"])
    calls = []
    from distributed_oracle_search_tpu.models.cpd import CPDOracle

    orig = CPDOracle.query
    # sequential rounds (a never-binding move budget skips the fusion)
    try:
        CPDOracle.query = lambda self, q, **kw: (
            calls.append(len(q)) or orig(self, q, **kw))
        _, _, records = _campaign(conf, str(tmp_path / "o"), 0,
                                  ["--k-moves", "1000000"])
    finally:
        CPDOracle.query = orig
    assert calls == [N_PAIRS, N_PAIRS]      # one call per round
    events = [r["event"] for r in records]
    assert "warm" not in events and events.count("part") == 2


def test_stage_spans_and_counters_per_part(dataset, tmp_path):
    conf = _conf(dataset, tmp_path, ["diff"])
    snap0 = obs_metrics.REGISTRY.snapshot()
    with obs_trace.capture() as cap:
        _, _, records = _campaign(conf, str(tmp_path / "o"), 25)
    snap1 = obs_metrics.REGISTRY.snapshot()
    n_parts = -(-N_PAIRS // 25)
    names = [ev["name"] for ev in cap.events]
    for stage in ("cpd.route", "cpd.walk", "cpd.fetch"):
        assert names.count(stage) == n_parts
    walks = [ev["args"] for ev in cap.events if ev["name"] == "cpd.walk"]
    assert [a["pairs"] for a in walks] == [25, 25, 25, 21]
    assert all(a["lanes"] >= a["pairs"] and a["lanes"] % 4 == 0
               for a in walks)

    def delta(kind, name):
        a, b = snap0[kind].get(name), snap1[kind][name]
        if kind == "histograms":
            return b["count"] - (a["count"] if a else 0)
        return b - (a or 0)

    pairs = delta("counters", "cpd_pairs_total")
    lanes = delta("counters", "cpd_lanes_total")
    assert pairs == N_PAIRS and lanes >= pairs
    assert lanes == sum(a["lanes"] for a in walks)
    for h in ("cpd_route_seconds", "cpd_walk_seconds",
              "cpd_fetch_seconds"):
        assert delta("histograms", h) == n_parts
    # the part records carry the same counters, cumulative
    parts = [r for r in records if r["event"] == "part"]
    got = (parts[-1]["metrics"]["cpd_pairs_total"]
           - parts[0]["metrics"]["cpd_pairs_total"])
    assert got == N_PAIRS - parts[0]["pairs"]


def test_no_compile_inside_the_rounds(dataset, tmp_path):
    conf = _conf(dataset, tmp_path, ["diff"])
    # a fresh routed width the earlier tests did not compile
    _, _, records = _campaign(conf, str(tmp_path / "o"), 7)
    parts = [r for r in records if r["event"] == "part"]
    lowered = [r["metrics"]["head_programs_lowered_total"] for r in parts]
    assert lowered[0] == lowered[-1]
    warm = next(r for r in records if r["event"] == "warm")
    assert warm["lanes"] and all(q & (q - 1) == 0 for q in warm["lanes"])
