"""Share of the dispatch loop's time blocked on an empty queue: timer
dispatch.idle / (dispatch.idle + dispatch.fill + dispatch.match_many), in %."""
SOURCE = "program_span"
LAYER = "front door and dispatcher"
MOVES = "traces_per_s"


def read(r):
    idle, fill, match = (r.timer_total(n) for n in (
        "dispatch.idle", "dispatch.fill", "dispatch.match_many"))
    if idle is None or fill is None or match is None:
        return None
    return r.ratio(idle, idle + fill + match, 100.0)
