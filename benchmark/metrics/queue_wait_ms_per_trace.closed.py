"""Dispatcher queue wait per trace: timer dispatch.queue_wait total /
counter dispatch.traces, in ms."""
SOURCE = "program_span"
LAYER = "front door and dispatcher"
MOVES = "traces_per_s"


def read(r):
    return r.ratio(r.timer_total("dispatch.queue_wait"),
                   r.counter("dispatch.traces"), 1e3)
