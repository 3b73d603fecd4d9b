"""Request tracing: trace_id/span_id contexts and trace-event export.

A span is one timed stage of one request's life: the service opens a
root span per ``/report`` request, and every ``metrics.timer`` site
(dispatch, prep, decode, assemble, report serialisation, tile egress)
nests a child span under it automatically, so the existing stage-timer
discipline IS the span tree. Spans propagate through a contextvar;
thread hops (the dispatcher queue, the matcher's device lanes) carry
the context explicitly via :func:`current`/:func:`attach` because a
queue handoff does not copy contexts.

Cost discipline (same as :mod:`..utils.faults`): when disarmed, every
span site pays ONE module-flag load — :func:`span` returns a shared
no-op context manager, :func:`current` returns None without touching
the contextvar. Arming is either persistent (``REPORTER_TPU_TRACE=1``
in the environment, or :func:`configure`) or per-request
(:func:`force_begin`/:func:`force_end`, the ``?trace=1`` debug flag —
the flag arms the whole process for the request's lifetime, so spans
on worker threads record too, and the exporter filters by trace id).

Completed spans land in :mod:`flightrec`'s bounded ring — the same
ring the crash postmortem dumps — and :func:`export_trace` renders one
trace's spans as Chrome/Perfetto trace-event JSON (``ph:"X"`` complete
events, epoch-microsecond timestamps, comparable across processes).
They do not line up with a profiler trace (``jax.profiler``,
``metrics.device_trace``), whose events are relative to its session's
start. What meets the device timeline is the stage timers themselves:
every ``metrics.timer`` also opens a ``jax.profiler.TraceAnnotation``
of its name on its thread's host line, on the profiler's clock.
"""
from __future__ import annotations

import contextvars
import itertools
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..utils import locks as _locks
from . import flightrec

ENV_VAR = "REPORTER_TPU_TRACE"

_ENABLED = False   # the one flag every disarmed span site loads
_ARMED = False     # persistent arming (env / configure)
_FORCED = 0        # ?trace=1 requests currently in flight
_lock = _locks.new_lock("trace.arm")

#: (trace_id, span_id) of the innermost open span in this context
_ctx: "contextvars.ContextVar[Optional[Tuple[str, int]]]" = \
    contextvars.ContextVar("reporter_tpu_trace", default=None)

#: process-unique span ids (itertools.count is atomic under the GIL)
_ids = itertools.count(1)

#: maps perf_counter_ns timestamps onto wall-clock epoch ns, so span
#: timestamps are comparable across processes
_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def _recompute_locked() -> None:
    global _ENABLED
    _ENABLED = _ARMED or _FORCED > 0


def configure(on: bool) -> None:
    """Persistently arm/disarm tracing (the env flag's in-process twin)."""
    global _ARMED
    with _lock:
        _ARMED = bool(on)
        _recompute_locked()


def force_begin() -> None:
    """Arm tracing for one in-flight request (``?trace=1``)."""
    global _FORCED
    with _lock:
        _FORCED += 1
        _recompute_locked()


def force_end() -> None:
    global _FORCED
    with _lock:
        _FORCED = max(0, _FORCED - 1)
        _recompute_locked()


def enabled() -> bool:
    return _ENABLED


class _Noop:
    """Shared do-nothing span/attach: the disarmed fast path allocates
    nothing and enters/exits in two attribute calls."""

    __slots__ = ()
    trace_id = None
    span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def new_trace_id() -> str:
    """Process-qualified trace id (pid keeps ids unique across the
    worker fleet without any coordination)."""
    return f"{os.getpid():x}-{next(_ids):012x}"


class _Span:
    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "_token", "_t0", "dur_ns")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs
        self.dur_ns = 0

    def __enter__(self):
        cur = _ctx.get()
        if cur is None:
            self.trace_id = new_trace_id()
            self.parent_id = 0
        else:
            self.trace_id, self.parent_id = cur
        self.span_id = next(_ids)
        self._token = _ctx.set((self.trace_id, self.span_id))
        self._t0 = time.perf_counter_ns()
        flightrec.span_opened(self.span_id, {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "t0_ns": self._t0 + _EPOCH_OFFSET_NS,
            "tid": threading.get_ident(),
            **({"attrs": self.attrs} if self.attrs else {})})
        return self

    def __exit__(self, *exc):
        self.dur_ns = time.perf_counter_ns() - self._t0
        _ctx.reset(self._token)
        flightrec.span_closed(self.span_id, self.dur_ns)
        return False


def span(name: str, **attrs):
    """A timed span context. Disarmed: one flag check, a shared no-op."""
    if not _ENABLED:
        return _NOOP
    return _Span(name, attrs or None)


def current() -> Optional[Tuple[str, int]]:
    """The (trace_id, span_id) context to carry across a thread hop;
    None when disarmed or outside any span."""
    if not _ENABLED:
        return None
    return _ctx.get()


class _Attach:
    __slots__ = ("ctx", "_token")

    def __init__(self, ctx: Tuple[str, int]):
        self.ctx = ctx

    def __enter__(self):
        self._token = _ctx.set(self.ctx)
        return self

    def __exit__(self, *exc):
        _ctx.reset(self._token)
        return False


def attach(ctx: Optional[Tuple[str, int]]):
    """Adopt a context captured by :func:`current` on another thread
    (the dispatcher loop, the matcher's device lanes)."""
    if ctx is None:
        return _NOOP
    return _Attach(ctx)


def phase_spans(names: Sequence[str], ns_list: Sequence[int]) -> None:
    """Synthesize back-to-back child spans ending now from phase
    durations measured inside an opaque call — the ABI-11 native prep
    ``phase_ns`` split becomes ``prep.candidates``/``select``/``routes``
    child spans without a second timing source. Phases overlap across
    prep worker threads, so the reconstruction is the serialised view
    (flagged ``synthetic`` in the attrs)."""
    if not _ENABLED:
        return
    cur = _ctx.get()
    if cur is None:
        return
    pairs = [(n, int(ns)) for n, ns in zip(names, ns_list) if ns > 0]
    if not pairs:
        return
    trace_id, parent_id = cur
    tid = threading.get_ident()
    end_ns = time.perf_counter_ns() + _EPOCH_OFFSET_NS
    offsets = list(itertools.accumulate(ns for _, ns in pairs))
    base_ns = end_ns - offsets[-1]
    flightrec.record_closed([
        {"name": name, "trace_id": trace_id, "span_id": next(_ids),
         "parent_id": parent_id, "t0_ns": base_ns + off - ns,
         "dur_ns": ns, "tid": tid, "attrs": {"synthetic": True}}
        for (name, ns), off in zip(pairs, offsets)])


# ---- export ----------------------------------------------------------------

def events_for(trace_id: str) -> List[dict]:
    """Closed span records for one trace, oldest first, from the ring."""
    return [r for r in flightrec.events() if r["trace_id"] == trace_id]


def to_trace_events(records: Iterable[dict],
                    in_flight: Iterable[dict] = ()) -> Dict[str, object]:
    """Chrome/Perfetto trace-event JSON object: completed spans as
    ``ph:"X"`` events (epoch-µs timestamps, µs durations), still-open
    spans as ``ph:"B"`` begin events — load the dict's JSON in
    ``chrome://tracing`` or https://ui.perfetto.dev."""
    pid = os.getpid()
    events = [
        {"name": r["name"], "ph": "X", "cat": "reporter_tpu",
         "pid": r.get("pid", pid), "tid": r["tid"],
         "ts": r["t0_ns"] / 1e3, "dur": r["dur_ns"] / 1e3,
         "args": {"trace_id": r["trace_id"], "span_id": r["span_id"],
                  "parent_id": r["parent_id"], **r.get("attrs", {})}}
        for r in records]
    events += [
        {"name": r["name"], "ph": "B", "cat": "reporter_tpu",
         "pid": r.get("pid", pid), "tid": r["tid"],
         "ts": r["t0_ns"] / 1e3,
         "args": {"trace_id": r["trace_id"], "span_id": r["span_id"],
                  "parent_id": r["parent_id"], "in_flight": True,
                  **r.get("attrs", {})}}
        for r in in_flight]
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_trace(root) -> Dict[str, object]:
    """The trace-event JSON for the trace a root span belongs to (the
    ``?trace=1`` response payload); empty when the span never armed."""
    if root is None or getattr(root, "trace_id", None) is None:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    return to_trace_events(events_for(root.trace_id))


def _configure_env() -> None:
    val = os.environ.get(ENV_VAR, "").strip().lower()
    if val and val not in ("0", "off", "false"):
        configure(True)


_configure_env()
