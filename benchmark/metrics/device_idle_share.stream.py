"""1 - union of device op intervals / traced window, in %."""
SOURCE = "device_trace"
LAYER = "device"
MOVES = "traces_per_s"


def read(r):
    return r.idle_share()
