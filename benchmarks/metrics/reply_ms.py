"""Mean time in the window from a pair frame's last answer to its reply
written (``gateway_reply_seconds``)."""

from harness import hist_mean


def read(run):
    return hist_mean(run, "gateway_reply_seconds", 1e3)
