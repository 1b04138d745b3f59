"""Mean host time in the window between one batch's answers on the host
and the next batch's walk launch (``worker_device_gap_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "worker_device_gap_seconds", 1e3)
