"""The carried-state rounds (pad, dispatch, sync and commit of each
round's device step) per report window: timer match.incremental.rounds
total / counter stream.windows, in ms."""
SOURCE = "program_span"
LAYER = "incremental advance"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("match.incremental.rounds"),
                   r.counter("stream.windows"), 1e3)
