"""Percent of the lanes the campaign's walks ran in the window that held
a pair: ``cpd_pairs_total`` over ``cpd_lanes_total`` (each worker's
group is padded to a power of two)."""


def read(run):
    if run.get("kind") != "campaign" or not run.get("scrapes"):
        return None
    before, after = run["scrapes"]
    if "cpd_lanes_total" not in after:
        return None
    pairs = after["cpd_pairs_total"] - before.get("cpd_pairs_total", 0)
    lanes = after["cpd_lanes_total"] - before.get("cpd_lanes_total", 0)
    return 100.0 * pairs / lanes if lanes > 0 else None
