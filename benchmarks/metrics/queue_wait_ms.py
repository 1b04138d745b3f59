"""Mean time a request waited in its shard's queue in the window, from
enqueued until popped into a batch (``serve_queue_wait_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "serve_queue_wait_seconds", 1e3)
