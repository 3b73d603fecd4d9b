"""The comparison that decides ``correct``: served answers against the
plain reference (``ref.pipeline``), request by request.

Two numbers, each over the requests sampled from the run:

- ``segment_mismatch``: the share of the reference's matched segments
  (every field of each ``segment_matcher`` entry, and each ``datastore``
  report) that the served body does not hold. A request that never got a
  200 answer misses all of its segments.
- ``trace_mismatch``: the share of sampled requests whose served answer
  misses an item of the reference's or holds one more.

Both are shares of the reference's answer, so a sound run reads near 0,
whatever the traffic's size. Limits are set in each configuration file
from readings of sound runs and of the control (``PERF.md``).
"""
from __future__ import annotations

#: times are written to the millisecond (``round(t, 3)``); the host's
#: float32 and the reference's float64 arithmetic may round a time to
#: neighbouring milliseconds, so times match within two of them
TIME_TOL_S = 0.002


def _seg(s: dict) -> tuple:
    return (("seg", s.get("segment_id"), tuple(s.get("way_ids") or ()),
             s.get("length"), s.get("queue_length"), s.get("internal"),
             s.get("begin_shape_index"), s.get("end_shape_index")),
            (s.get("start_time"), s.get("end_time")))


def _rep(r: dict) -> tuple:
    return (("rep", r.get("id"), r.get("length"), r.get("queue_length"),
             r.get("next_id")), (r.get("t0"), r.get("t1")))


def _items(body: dict) -> list:
    segs = body.get("segment_matcher", {}).get("segments", [])
    reps = body.get("datastore", {}).get("reports", [])
    return [_seg(s) for s in segs] + [_rep(r) for r in reps]


def _close(a: tuple, b: tuple) -> bool:
    return all(x == y or (isinstance(x, float) and isinstance(y, float)
                          and abs(x - y) <= TIME_TOL_S)
               for x, y in zip(a, b))


def compare(served: "dict | None", expected: dict) -> tuple:
    """(items of the reference's answer missing from the served one,
    items in the reference's answer, whether the answers differ). An item
    is a matched segment or a datastore report: its ids, lengths and
    shape indices equal, its times within ``TIME_TOL_S``."""
    want = _items(expected)
    if served is None:
        return len(want), len(want), True
    have = {}
    got = _items(served)
    for key, times in got:
        have.setdefault(key, []).append(times)
    missing = 0
    for key, times in want:
        pool = have.get(key, [])
        hit = next((k for k, t in enumerate(pool) if _close(t, times)),
                   None)
        if hit is None:
            missing += 1
        else:
            pool.pop(hit)
    return missing, len(want), bool(missing) or len(got) != len(want)


class Tally:
    """Sums :func:`compare` over a sample."""

    def __init__(self):
        self.missing = self.total = self.differ = self.n = 0
        self.unanswered = 0

    def add(self, served: "dict | None", expected: dict) -> None:
        m, t, d = compare(served, expected)
        self.missing += m
        self.total += t
        self.differ += bool(d)
        self.n += 1
        self.unanswered += served is None

    def numbers(self) -> dict:
        return {
            "segment_mismatch": self.missing / self.total if self.total
            else 1.0,
            "trace_mismatch": self.differ / self.n if self.n else 1.0,
            "compared": self.n, "unanswered": self.unanswered,
        }
