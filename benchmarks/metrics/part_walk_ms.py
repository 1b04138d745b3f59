"""Mean time per part in the campaign's window of its walk: the routed
pairs and the weights to the chips, the launch, until every chip's
answers are ready (``cpd_walk_seconds``, span ``cpd.walk``)."""

from harness import hist_mean


def read(run):
    if run.get("kind") != "campaign":
        return None
    return hist_mean(run, "cpd_walk_seconds", 1e3)
