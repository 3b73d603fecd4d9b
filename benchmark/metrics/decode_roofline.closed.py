"""Least time the window's decode calls need at the chip's peaks (kernels/decode.py) / their device time, in %."""
SOURCE = "device_trace"
LAYER = "decode"
MOVES = "traces_per_s"


def read(r):
    return r.roofline("decode")
