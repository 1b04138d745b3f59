"""Mean time the engine took in the window to bring a batch's answers to
the host in request order after its walk (``worker_fetch_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "worker_fetch_seconds", 1e3)
