"""Percent of the chip's idle time in the traced span of a serve cell
during which one of the runner thread's stage spans was open
(``serve.wait``, ``worker.prep``, ``worker.walk``, ``worker.fetch``,
``serve.finish``; ``host_spans.py``): how much of the idle time the
program's spans can name."""

import harness
from host_spans import for_run


def read(run):
    if run.get("kind") != "serve":
        return None
    att = for_run(run, harness.WORK)
    if att is None or att["idle_ns"] <= 0:
        return None
    return 100.0 * att["attributed_ns"] / att["idle_ns"]
