"""Report windows the carried-state path answered / all report windows
answered: counter match.incremental.matches / counter stream.windows,
in %. The rest went through the windowed decode."""
SOURCE = "program_counter"
LAYER = "incremental advance"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.counter("match.incremental.matches"),
                   r.counter("stream.windows"), 100.0)
