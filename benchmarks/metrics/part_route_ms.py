"""Mean time per part in the campaign's window of its host routing:
the pairs grouped by owner, ordered by length estimate and padded to
the chips' lanes (``cpd_route_seconds``, span ``cpd.route``)."""

from harness import hist_mean


def read(run):
    if run.get("kind") != "campaign":
        return None
    return hist_mean(run, "cpd_route_seconds", 1e3)
