"""Percent of the traced span in which no operation ran on the chip
(averaged over the chips), in a campaign cell."""

from trace_reduce import idle_pct


def read(run):
    return (idle_pct(run.get("trace")) if run.get("kind") == "campaign"
            else None)
