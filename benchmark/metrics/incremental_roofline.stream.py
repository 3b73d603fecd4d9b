"""Least time the window's carried-state steps need at the chip's peaks
(kernels/incremental.py) / their device time, in %."""
SOURCE = "device_trace"
LAYER = "incremental advance"
MOVES = "traces_per_s"


def read(r):
    return r.roofline("incremental")
