"""Traces per dispatcher batch: counters dispatch.traces / dispatch.batches."""
SOURCE = "program_counter"
LAYER = "front door and dispatcher"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.counter("dispatch.traces"), r.counter("dispatch.batches"))
