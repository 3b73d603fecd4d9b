"""Host prep of the appended points (candidates, kept-point selection,
route rows, wire cast) per report window: timer match.incremental.prep
total / counter stream.windows, in ms."""
SOURCE = "program_span"
LAYER = "incremental advance"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("match.incremental.prep"),
                   r.counter("stream.windows"), 1e3)
