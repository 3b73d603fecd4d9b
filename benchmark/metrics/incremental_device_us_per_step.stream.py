"""Device time of the carried-state step's modules in the traced window
/ counter match.incremental.steps (trace steps advanced), in us."""
SOURCE = "device_trace"
LAYER = "incremental advance"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.kernel_seconds("incremental"),
                   r.counter("match.incremental.steps"), 1e6)
