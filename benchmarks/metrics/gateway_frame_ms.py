"""Mean time in the window from a query frame read off the gateway's
socket to its reply written (``gateway_frame_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "gateway_frame_seconds", 1e3)
