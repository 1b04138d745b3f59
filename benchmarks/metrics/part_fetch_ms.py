"""Mean time per part in the campaign's window of its fetch: the
answers to the host and back in scenario order (``cpd_fetch_seconds``,
span ``cpd.fetch``)."""

from harness import hist_mean


def read(run):
    if run.get("kind") != "campaign":
        return None
    return hist_mean(run, "cpd_fetch_seconds", 1e3)
