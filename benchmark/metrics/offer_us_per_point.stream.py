"""The worker's offer loop per message (format, batch, punctuation
checks), flushes left out: (timer stream.ingest - timer
stream.report_flush - timer stream.tiles) totals / counter
stream.points, in us."""
SOURCE = "program_span"
LAYER = "stream worker"
MOVES = "traces_per_s"


def read(r):
    ingest = r.timer_total("stream.ingest")
    if ingest is None:
        return None
    rest = ingest - (r.timer_total("stream.report_flush") or 0.0) \
        - (r.timer_total("stream.tiles") or 0.0)
    return r.ratio(rest, r.counter("stream.points"), 1e6)
