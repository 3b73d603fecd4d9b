"""Front door per trace: timers service.headers + service.parse +
service.columns + service.respond totals / counter dispatch.traces, in ms."""
SOURCE = "program_span"
LAYER = "front door and dispatcher"
MOVES = "traces_per_s"
STAGES = ("service.headers", "service.parse", "service.columns",
          "service.respond")


def read(r):
    totals = [r.timer_total(s) for s in STAGES]
    if any(t is None for t in totals):
        return None
    return r.ratio(sum(totals), r.counter("dispatch.traces"), 1e3)
