"""Handler threads' CPU time over their wall time in the timed front-door
and wire stages, in %: over service.headers, service.parse,
service.columns and report.serialise, the sum of the timers <stage>.cpu
over the sum of <stage>.cpu_wall (the same sampled executions)."""
SOURCE = "program_span"
LAYER = "front door and dispatcher"
MOVES = "traces_per_s"
STAGES = ("service.headers", "service.parse", "service.columns",
          "report.serialise")


def read(r):
    cpu = [r.timer_total(s + ".cpu") for s in STAGES]
    wall = [r.timer_total(s + ".cpu_wall") for s in STAGES]
    if any(t is None for t in cpu + wall):
        return None
    return r.ratio(sum(cpu), sum(wall), 100.0)
