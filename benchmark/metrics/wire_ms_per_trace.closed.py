"""Response writer per trace: timer report.serialise total / counter
dispatch.traces, in ms."""
SOURCE = "program_span"
LAYER = "assembly and wire"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("report.serialise"),
                   r.counter("dispatch.traces"), 1e3)
