"""Device time of the decode kernel's modules in the traced window / traces dispatched, in us."""
SOURCE = "device_trace"
LAYER = "decode"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.kernel_seconds("decode"), r.counter("dispatch.traces"), 1e6)
