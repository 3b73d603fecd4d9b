"""Collector pauses per trace: timer process.gc.pause total (every pass
of every generation) / counter dispatch.traces, in ms."""
SOURCE = "program_span"
LAYER = "host runtime"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("process.gc.pause"),
                   r.counter("dispatch.traces"), 1e3)
