"""Mean time a flushed batch waited in the window for the runner to take
it from the micro-batcher's handoff (``serve_handoff_wait_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "serve_handoff_wait_seconds", 1e3)
