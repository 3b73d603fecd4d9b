"""Host prep per trace: timer matcher.prep total / counter dispatch.traces, in ms."""
SOURCE = "program_span"
LAYER = "host prep"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("matcher.prep"), r.counter("dispatch.traces"), 1e3)
