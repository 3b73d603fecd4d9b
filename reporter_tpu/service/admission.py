"""SLO-driven admission control + the graceful-degradation ladder.

Nothing in the serving tier said "no" before this module: every request
was admitted no matter how far p99 had blown past ``REPORTER_TPU_SLO_MS``,
the dispatcher queue grew without bound, and overload meant collapse —
every request slow — instead of a bounded number of requests shed. This
is the overload-control layer ROADMAP's "millions of users at peak, and
it bends instead of breaking" direction calls for, using the PR 7 SLO
budgets and the PR 8 queue-depth gauges as *sensors*:

- **AdmissionGate** — the front door. Before a /report request is even
  parsed, the gate reads three live sensors and sheds with HTTP 429 +
  a computed ``Retry-After`` (utils/http.py clients already honour it)
  rather than queueing work that cannot meet its deadline:

  * ``queue``     the dispatcher backlog, both as a hard bound (the
                  ``REPORTER_TPU_QUEUE_MAX`` queue is full) and as a
                  *deadline* check — predicted queue wait (depth x the
                  dispatcher's EWMA per-trace service time) exceeding
                  ``DEADLINE_FRACTION`` of the SLO budget means the
                  request would breach before it even dispatched;
  * ``slo``       the *windowed* p99 of each budgeted stage breaching
                  its ``REPORTER_TPU_SLO_MS`` target — windowed via
                  bucket-count deltas of the cumulative histograms, so
                  the sensor recovers when load drops (a lifetime p99
                  never forgets one bad minute);
  * ``inflight``  admitted-but-unanswered requests over
                  ``REPORTER_TPU_INFLIGHT_MAX``.

  Every shed is counted per reason (``admission.shed.{queue,slo,
  inflight}``); an admission-path failure (the ``admission.gate``
  failpoint, a sensor exception) FAILS OPEN — admit, count
  ``admission.errors`` — because a broken gate must degrade to PR-13
  behaviour (serve everything), never to shedding everything.

- **PressureLadder** — under *sustained* pressure the service steps
  down feature-by-feature instead of dying, one named rung at a time
  with hysteresis (a rung must hold for ``REPORTER_TPU_PRESSURE_HOLD_S``
  before the next step; stepping back up needs twice that calm, so the
  ladder cannot flap):

      normal -> shed_shadow -> shed_trace -> coarse_buckets -> oracle_decode

  * ``shed_shadow``    shadow-accuracy sampling suspended (the oracle
                       thread's CPU goes back to serving);
  * ``shed_trace``     per-request ``?trace=1`` tracing refused
                       (span/export overhead shed);
  * ``coarse_buckets`` the chunk planner classes every trace at its
                       ladder bucket (``batchpad.plan_chunks``) — fewer,
                       larger decode shapes and no fresh compile
                       episodes mid-storm;
  * ``oracle_decode``  the last rung: decode serves via the numpy
                       oracle path (the PR 9 circuit fallback), keeping
                       the device queue free for the drain backlog.

  Transitions are logged, counted (``pressure.transitions`` +
  ``pressure.enter.<rung>``), and surfaced as the ``pressure`` block on
  ``/health`` and the worker heartbeat.

Both halves arm via ``REPORTER_TPU_ADMISSION=1`` (default off: the gate
is a serving-fleet policy, not a test-suite default). The module state
is process-wide by design — one ladder per process, like the profiler —
and resets in forked pre-fork workers via the ``utils.forksafe`` hook
(a child must not inherit the parent's pressure level).
"""
from __future__ import annotations

import logging
import math
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..obs import slo
from ..utils import faults, metrics
from ..utils import forksafe as _forksafe
from ..utils import locks as _locks
from ..utils.runtime import _env_float, _env_int

logger = logging.getLogger("reporter_tpu.admission")

ENV_ADMISSION = "REPORTER_TPU_ADMISSION"
ENV_INFLIGHT = "REPORTER_TPU_INFLIGHT_MAX"
ENV_HOLD = "REPORTER_TPU_PRESSURE_HOLD_S"

#: fraction of the tightest SLO budget the predicted queue wait may
#: consume before the gate sheds on the deadline check — the remaining
#: half covers the admitted request's own batch (gather + service):
#: with REPORTER_TPU_BATCH_LATENCY_MS at ~budget/4 the worst case sums
#: comfortably inside the budget
DEADLINE_FRACTION = 0.5

#: Retry-After clamp: at least 1 s (sub-second retries re-arrive inside
#: the same overload), at most 30 s (a misestimated EWMA must not park
#: honest clients for minutes)
RETRY_AFTER_MIN_S = 1
RETRY_AFTER_MAX_S = 30

#: how often the gate refreshes its windowed-p99 sensor; between
#: refreshes admit() costs two integer reads and a couple of compares
EVAL_INTERVAL_S = 0.25


class Overload(RuntimeError):
    """A request shed by load management (admission gate or the bounded
    dispatcher queue). ``reason`` is the counted shed family; the
    serving layer maps this to HTTP 429 with ``Retry-After:
    ceil(retry_after_s)``."""

    def __init__(self, reason: str, retry_after_s: float):
        super().__init__(f"overloaded ({reason}); retry after "
                         f"{retry_after_s:.0f}s")
        self.reason = reason
        self.retry_after_s = retry_after_s


def armed() -> bool:
    """Whether admission control is armed (``REPORTER_TPU_ADMISSION``);
    read per service build, not cached — a test or operator flips it
    between constructions."""
    return os.environ.get(ENV_ADMISSION, "").strip().lower() \
        not in ("", "0", "off", "false", "no")


def retry_after_s(depth: int, ewma_s: Optional[float]) -> int:
    """The computed back-off a shed response carries: the expected time
    for the current backlog to drain (depth x per-trace EWMA), clamped.
    With no service-time estimate yet, the floor — an honest "soon"."""
    if not ewma_s or depth <= 0:
        return RETRY_AFTER_MIN_S
    return int(min(max(math.ceil(depth * ewma_s), RETRY_AFTER_MIN_S),
                   RETRY_AFTER_MAX_S))


# ---- windowed p99 ----------------------------------------------------------

class WindowedQuantile:
    """p99 over a sliding window of a cumulative stage histogram.

    The metrics timers are monotone (they never forget), so a lifetime
    p99 that breached once stays breached forever — useless as an
    admission sensor, which must notice *recovery*. This helper diffs
    the fixed log-bucket counts between evaluations: the diff IS the
    window's histogram, and its p99 is the window's p99. An idle window
    (no new observations) reports None — an idle stage is not a slow
    one, matching obs/slo.py's posture.
    """

    def __init__(self, registry: Optional[metrics.Registry] = None):
        self._registry = registry if registry is not None \
            else metrics.default
        self._prev: Dict[str, Tuple[int, List[int]]] = {}

    def update(self, stages: List[str]) -> Dict[str, Optional[float]]:
        """One evaluation: {stage: windowed p99 seconds or None}."""
        _counters, timers = self._registry.export_state()
        out: Dict[str, Optional[float]] = {}
        for stage in stages:
            got = timers.get(stage)
            if got is None:
                out[stage] = None
                continue
            count, _total, max_s, buckets = got
            prev_count, prev_buckets = self._prev.get(stage, (0, None))
            self._prev[stage] = (count, buckets)
            window = count - prev_count
            if window <= 0:
                out[stage] = None
                continue
            if prev_buckets is None:
                diff = buckets
            else:
                diff = [b - p for b, p in zip(buckets, prev_buckets)]
            out[stage] = self._quantile(diff, window, 0.99, max_s)
        return out

    @staticmethod
    def _quantile(diff: List[int], total: int, q: float,
                  max_s: float) -> float:
        """Within-bucket linear interpolation, the same scheme as
        metrics._Timer.quantile — the raw log2 bucket UPPER bound
        would overestimate by up to 2x, and a 2x-high p99 sensor
        sheds traffic that is actually inside budget."""
        bounds = metrics.BUCKET_BOUNDS_S
        target = q * total
        cum = 0
        for idx, n in enumerate(diff):
            below = cum
            cum += n
            if cum >= target:
                lo = bounds[idx - 1] if 0 < idx <= len(bounds) else 0.0
                hi = bounds[idx] if idx < len(bounds) else max_s
                frac = (target - below) / n if n else 1.0
                return min(lo + frac * (hi - lo), max_s)
        return max_s


# ---- the degradation ladder ------------------------------------------------

#: the named rungs, mildest first; index == pressure level
RUNGS = ("normal", "shed_shadow", "shed_trace", "coarse_buckets",
         "oracle_decode")


class PressureLadder:
    """Sustained-pressure step-down with hysteresis.

    :meth:`observe` feeds one boolean pressure sample (typically "did
    the gate shed / breach this evaluation window"). A condition must
    hold continuously for ``hold_s`` before the ladder steps DOWN one
    rung (toward degradation), and for ``2 * hold_s`` of calm before it
    steps back UP — and at most one rung moves per hold interval, so a
    spike cannot slam the service to the oracle path and a lull cannot
    snap every feature back at once.
    """

    def __init__(self, hold_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.hold_s = hold_s if hold_s is not None \
            else _env_float(ENV_HOLD, 2.0)
        self.clock = clock
        self._lock = _locks.new_lock("admission.ladder")
        self.level = 0
        now = clock()
        self._cond = False      # last observed pressure condition
        self._cond_t = now      # when that condition began
        self._trans_t = now     # last transition time
        self.transitions = 0

    def observe(self, pressured: bool) -> int:
        """Feed one pressure sample; returns the (possibly new) level.
        Transitions apply their rung effects outside the ladder lock."""
        new_level = None
        with self._lock:
            now = self.clock()
            if pressured != self._cond:
                self._cond = pressured
                self._cond_t = now
            dwell = now - self._cond_t
            since_trans = now - self._trans_t
            if pressured and self.level < len(RUNGS) - 1 \
                    and dwell >= self.hold_s \
                    and since_trans >= self.hold_s:
                self.level += 1
                self._trans_t = now
                self.transitions += 1
                new_level = self.level
            elif not pressured and self.level > 0 \
                    and dwell >= 2.0 * self.hold_s \
                    and since_trans >= 2.0 * self.hold_s:
                self.level -= 1
                self._trans_t = now
                self.transitions += 1
                new_level = self.level
            level = self.level
        if new_level is not None:
            _apply_level(new_level)
            metrics.count("pressure.transitions")
            metrics.count(f"pressure.enter.{RUNGS[new_level]}")
            logger.warning("pressure ladder -> %s (level %d/%d)",
                           RUNGS[new_level], new_level, len(RUNGS) - 1)
        return level

    def snapshot(self) -> dict:
        with self._lock:
            return {"level": self.level,
                    "state": RUNGS[self.level],
                    "rungs": list(RUNGS),
                    "transitions": self.transitions,
                    "hold_s": self.hold_s}


def _apply_level(level: int) -> None:
    """Push the rung effects into their owning modules (cold path: runs
    only on a transition). Each effect is a module flag the hot path
    reads with one global load; lazy imports keep this module free of
    matcher/profiler import cycles."""
    from ..obs import profiler
    profiler.set_shadow_suspended(level >= 1)
    global _trace_shed
    with _module_lock:
        _trace_shed = level >= 2
    from ..matcher import incremental
    incremental.set_pressure_shed(level >= 2)
    from ..matcher import batchpad
    batchpad.set_pressure_coarse(level >= 3)
    from ..matcher import matcher as matcher_mod
    matcher_mod.set_pressure_oracle(level >= 4)


# ---- process-wide ladder state ---------------------------------------------

_module_lock = _locks.new_lock("admission.module")
_ladder: Optional[PressureLadder] = None
_trace_shed = False


def ladder(hold_s: Optional[float] = None,
           clock: Callable[[], float] = time.monotonic
           ) -> PressureLadder:
    """The process-wide ladder, created on first use (one ladder per
    process: every gate in the process feeds it, every consumer —
    /health, the heartbeat, the rung flags — reads it)."""
    global _ladder
    with _module_lock:
        if _ladder is None:
            _ladder = PressureLadder(hold_s=hold_s, clock=clock)
        return _ladder


def current_level() -> int:
    lad = _ladder
    return lad.level if lad is not None else 0


def allow_request_trace() -> bool:
    """Whether per-request ``?trace=1`` tracing is currently allowed
    (the ``shed_trace`` rung refuses it under pressure)."""
    return not _trace_shed


def pressure_snapshot() -> dict:
    """The /health "pressure" block (also carried by the worker
    heartbeat): current ladder state, or the quiescent shape when no
    ladder was ever armed."""
    lad = _ladder
    if lad is None:
        return {"level": 0, "state": RUNGS[0], "transitions": 0}
    return lad.snapshot()


def _reset_module() -> None:
    """Forksafe / test reset: a forked worker (or the next test) must
    start at pressure zero with every rung effect withdrawn. The rung
    effects are only withdrawn when a ladder actually existed — the
    hook runs on EVERY fork in the process (subprocess's transient
    fork-exec children included) and must not import the matcher stack
    into a child that never armed admission."""
    global _ladder, _trace_shed
    with _module_lock:
        had = _ladder is not None
        _ladder = None
        _trace_shed = False
    if had:
        _apply_level(0)


_forksafe.register(_reset_module)


# ---- the admission gate ----------------------------------------------------

class AdmissionGate:
    """The /report front door: admit (and track in-flight) or shed.

    ``dispatcher`` duck-types :class:`..service.dispatch.BatchDispatcher`
    (``queue_depth()``, ``service_ewma_s()``, ``queue_max``). The gate
    is built per service (pre-fork workers each build their own post-
    fork) but feeds the ONE process-wide pressure ladder.
    """

    def __init__(self, dispatcher,
                 inflight_max: Optional[int] = None,
                 hold_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[metrics.Registry] = None):
        self.dispatcher = dispatcher
        if inflight_max is None:
            inflight_max = _env_int(ENV_INFLIGHT, 0)
        if inflight_max <= 0:
            # default: four full device batches of admitted work — the
            # dispatcher pipeline stays fed without the handler pool
            # itself becoming an unbounded queue
            inflight_max = 4 * max(1, getattr(dispatcher, "max_batch",
                                              64))
        self.inflight_max = inflight_max
        self.clock = clock
        self._lock = _locks.new_lock("admission.gate")
        self._inflight = 0
        self._window = WindowedQuantile(registry)
        self._last_eval = 0.0
        self._slo_breaches: List[str] = []
        self.ladder = ladder(hold_s=hold_s, clock=clock)
        self._shed_in_window = False

    # -- sensors ----------------------------------------------------------
    def _maybe_refresh(self, now: float) -> None:
        """Rate-limited sensor refresh: ONE thread per interval wins
        the locked check-and-set and recomputes the windowed p99s —
        an unlocked check would let a second refresher consume an
        empty bucket window and wipe the first's breach verdict (and
        clobber a concurrent shed sample). The winner also feeds the
        ladder one pressure sample."""
        with self._lock:
            if now - self._last_eval < EVAL_INTERVAL_S:
                return
            self._last_eval = now
            shed_seen = self._shed_in_window
            self._shed_in_window = False
        targets = slo.thresholds()
        breaches: List[str] = []
        if targets:
            p99s = self._window.update(sorted(targets))
            breaches = [stage for stage, budget in targets.items()
                        if p99s.get(stage) is not None
                        and p99s[stage] > budget]
        self._slo_breaches = breaches
        self.ladder.observe(bool(breaches) or shed_seen)

    def _evaluate(self) -> Optional[Overload]:
        self._maybe_refresh(self.clock())
        depth = self.dispatcher.queue_depth()
        ewma = self.dispatcher.service_ewma_s()
        qmax = getattr(self.dispatcher, "queue_max", 0)
        # the hard bound watches QUEUED work only (queued_depth):
        # queue_depth() also counts the batch in service, and a
        # max_batch larger than the bound would then read as
        # permanently full — shedding everything for every batch wall
        queued = getattr(self.dispatcher, "queued_depth",
                         self.dispatcher.queue_depth)()
        if qmax and queued >= qmax:
            return Overload("queue", retry_after_s(depth, ewma))
        targets = slo.thresholds()
        if targets and ewma and depth:
            budget = min(targets.values())
            if depth * ewma > DEADLINE_FRACTION * budget:
                # the deadline check: this request would spend its SLO
                # budget waiting in the queue — shed it NOW, while the
                # 429 is cheap, instead of serving a guaranteed breach
                return Overload("queue", retry_after_s(depth, ewma))
        if self._slo_breaches:
            return Overload("slo", retry_after_s(depth, ewma))
        return None

    # -- the gate ---------------------------------------------------------
    def admit(self) -> Optional[Overload]:
        """None = admitted (in-flight slot held until :meth:`release`);
        an :class:`Overload` = shed, counted per reason. A gate-path
        failure fails OPEN: a broken sensor serves everything."""
        try:
            faults.failpoint("admission.gate")
            verdict = self._evaluate()
            if verdict is None:
                # atomic compare-and-increment: a check in _evaluate
                # followed by a separate increment would let N
                # concurrent admits all pass at inflight_max - 1 and
                # overshoot the cap — the exact race the cap exists
                # to close
                with self._lock:
                    if self._inflight >= self.inflight_max:
                        verdict = Overload(
                            "inflight",
                            retry_after_s(
                                self.dispatcher.queue_depth(),
                                self.dispatcher.service_ewma_s()))
                    else:
                        self._inflight += 1
        except Exception as e:
            metrics.count("admission.errors")
            logger.error("admission gate failed open: %s", e)
            # fail-open admits still hold a slot: the caller WILL call
            # release(), and an unpaired decrement would leak capacity
            # out of the cap's books
            with self._lock:
                self._inflight += 1
            metrics.count("admission.admitted")
            return None
        if verdict is None:
            metrics.count("admission.admitted")
            return None
        metrics.count(f"admission.shed.{verdict.reason}")
        with self._lock:
            self._shed_in_window = True
        return verdict

    def release(self) -> None:
        """The admitted request answered (any status): free its slot."""
        with self._lock:
            if self._inflight > 0:
                self._inflight -= 1

    def tick(self) -> None:
        """Sensor/ladder heartbeat for idle periods: /health calls this
        so a service that stopped receiving traffic still steps the
        ladder back up (observe() only runs on admissions otherwise)."""
        self._maybe_refresh(self.clock())

    def snapshot(self) -> dict:
        with self._lock:
            inflight = self._inflight
        reg = metrics.default
        return {
            "armed": True,
            "inflight": inflight,
            "inflight_max": self.inflight_max,
            "queue_depth": self.dispatcher.queue_depth(),
            "queue_max": getattr(self.dispatcher, "queue_max", 0),
            "service_ewma_ms": round(
                (self.dispatcher.service_ewma_s() or 0.0) * 1000.0, 3),
            "slo_breaches": list(self._slo_breaches),
            "admitted": reg.counter("admission.admitted"),
            "shed": {reason: reg.counter(f"admission.shed.{reason}")
                     for reason in ("queue", "slo", "inflight")},
            "errors": reg.counter("admission.errors"),
        }


__all__ = ["AdmissionGate", "PressureLadder", "WindowedQuantile",
           "Overload", "RUNGS", "armed", "retry_after_s", "ladder",
           "current_level", "allow_request_trace", "pressure_snapshot"]
