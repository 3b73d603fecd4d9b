"""Tile flushes (cull, CSV rows, egress) per report window: timer
stream.tiles total / counter stream.windows, in ms."""
SOURCE = "program_span"
LAYER = "tile rows"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("stream.tiles"),
                   r.counter("stream.windows"), 1e3)
