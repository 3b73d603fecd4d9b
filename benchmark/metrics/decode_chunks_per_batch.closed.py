"""Decode chunks a dispatcher batch: decode.chunks / dispatch.batches.

Nothing from a program that does not count decode.chunks."""
SOURCE = "program_counter"
LAYER = "host prep"
MOVES = "traces_per_s"


def read(r):
    if "decode.chunks" not in r.counters:
        return None
    return r.ratio(r.counter("decode.chunks"), r.counter("dispatch.batches"))
