"""Lightweight metrics: counters, histogram stage timers, optional
device profiling.

The reference's only telemetry is a throughput counter logged every 10k
messages (reference: KeyedFormattingProcessor.java:36-38,
cat_to_kafka.py:59-61) and the per-trace stats block in the /report
response (reporter_service.py:164-177). SURVEY.md §5 lists
tracing/profiling as an absent subsystem to build fresh.

This module is that subsystem, kept deliberately small and lock-cheap:

- ``Registry``: named monotonically-increasing counters and stage
  timers. A timer is a fixed log-bucketed histogram (power-of-2 bounds,
  one list-slot increment per observation) plus count/total/max, so
  ``snapshot()`` reports p50/p95/p99 per stage — count/total/max alone
  cannot distinguish "steady 10 ms" from "9 ms with a 2 s tail", and
  the tail is what pages people.
- ``timer(name)``: context manager recording a stage duration. When
  request tracing is armed (``obs.trace``) every timer site doubles as
  a span site — the stage-timer discipline IS the span tree. Once the
  process has imported JAX, every timer also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so each stage sits
  on its thread's host line of any profiler trace, on the same clock as
  the device's operations (idle with no profiler session: ~0.4 µs).
  ``timer(name, cpu=True)`` also records, on one execution in
  ``CPU_SAMPLE_EVERY``, ``<name>.cpu`` (the calling thread's CPU time
  over the stage) and ``<name>.cpu_wall`` (the same executions' wall
  time): wall minus CPU is time the thread was runnable but waiting
  (the interpreter lock, on a Python stage).
- ``name_os_thread(name)``: names the calling thread where the profiler
  reads it, so its host line says which thread it is.
- ``install_gc_timer()``: one ``gc.callbacks`` hook per process timing
  every collector pass as ``process.gc.pause`` (with a ``process.gc``
  annotation), so collector stalls show on ``/stats`` and in a trace.
- ``device_trace(out_dir)``: context manager wrapping
  ``jax.profiler.trace`` — a real TPU trace viewable in TensorBoard
  or Perfetto — gated so importing this module never imports jax.

Snapshots report RAW floats: the old 6-decimal rounding collapsed
sub-microsecond timer means to 0.0, which read as "stage never ran".
Rounding is the wire writer's job — ``/stats`` serialises through
:func:`snapshot_rounded` (9 decimals, nanosecond resolution).

All state lives in a process-global default registry (``metrics.default``)
because every consumer in this framework is process-wide (one matcher, one
dispatcher); tests construct private ``Registry`` instances.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import math
import sys
import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from ..obs import trace as _trace
from . import locks as _locks

#: histogram bucket upper bounds in seconds: powers of two from ~1 µs
#: (2^-20) to 128 s (2^7). Log-spaced buckets keep relative error
#: bounded (<= 2x anywhere) with a bucket index that is one frexp —
#: no search — and 28 bounds cover every stage this framework times
#: (sub-µs flag checks to multi-second cold compiles). One extra
#: overflow bucket catches anything slower.
_BUCKET_EXP_MIN = -20
_BUCKET_EXP_MAX = 7
BUCKET_BOUNDS_S: Tuple[float, ...] = tuple(
    2.0 ** e for e in range(_BUCKET_EXP_MIN, _BUCKET_EXP_MAX + 1))
_N_BUCKETS = len(BUCKET_BOUNDS_S) + 1  # + overflow


def bucket_index(elapsed_s: float) -> int:
    """Histogram bucket for a duration: ``frexp`` exponent, clipped.
    A value in (2^(e-1), 2^e] lands in the bucket bounded by 2^e."""
    if elapsed_s <= 0.0:
        return 0
    # frexp(x) = (m, e) with x = m * 2^e, m in [0.5, 1) — so e is the
    # ceil of log2(x) for non-powers; exact powers land one higher,
    # which still satisfies the le-bound contract (x <= 2^e)
    e = math.frexp(elapsed_s)[1]
    idx = e - _BUCKET_EXP_MIN
    if idx < 0:
        return 0
    if idx >= _N_BUCKETS:
        return _N_BUCKETS - 1
    return idx


class _Timer:
    __slots__ = ("count", "total_s", "max_s", "buckets")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        # a plain list: one slot increment is several times cheaper
        # than a numpy scalar store, and every timed stage pays it
        self.buckets = [0] * _N_BUCKETS

    def add(self, elapsed_s: float) -> None:
        self.count += 1
        self.total_s += elapsed_s
        if elapsed_s > self.max_s:
            self.max_s = elapsed_s
        self.buckets[bucket_index(elapsed_s)] += 1

    def quantile(self, q: float) -> float:
        """Histogram quantile: find the bucket holding the q-th ranked
        observation, interpolate linearly inside it, clamp to the
        observed max (the last bucket is open-ended)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = np.cumsum(self.buckets)
        idx = int(np.searchsorted(cum, target, side="left"))
        lo = BUCKET_BOUNDS_S[idx - 1] if idx > 0 else 0.0
        hi = BUCKET_BOUNDS_S[idx] if idx < len(BUCKET_BOUNDS_S) \
            else self.max_s
        below = int(cum[idx - 1]) if idx > 0 else 0
        in_bucket = int(self.buckets[idx])
        frac = (target - below) / in_bucket if in_bucket else 1.0
        return min(lo + frac * (hi - lo), self.max_s)


#: a ``cpu=True`` stage reads the thread's CPU clock on one execution in
#: this many: the read is a system call (no vDSO path), several
#: microseconds apiece under some hypervisors, twice per stage, under
#: the interpreter lock
CPU_SAMPLE_EVERY = 16

#: queued timer observations (``Registry._record``) folded into the
#: histograms by the appender that reaches this many, lock permitting
_FOLD_AT = 64

#: ``jax.profiler.TraceAnnotation`` once JAX is imported (see
#: :func:`_annotation`); None until then
_ANNOTATION = None


def _annotation():
    """The profiler's annotation class, resolved once from
    ``sys.modules``: a process that never imported JAX imports nothing
    here and pays one dict lookup per timer."""
    global _ANNOTATION
    if _ANNOTATION is None:
        _ANNOTATION = getattr(sys.modules.get("jax.profiler"),
                              "TraceAnnotation", None)
    return _ANNOTATION


class _Stage:
    """One timed stage (:meth:`Registry.timer`): the profiler annotation
    outermost, then the request span, then the wall and CPU clocks."""

    __slots__ = ("_reg", "_name", "_cpu_name", "_ann", "_span", "_t0",
                 "_c0")

    def __init__(self, reg: "Registry", name: str, cpu: bool):
        self._reg = reg
        self._name = name
        self._cpu_name = name + ".cpu" if cpu else None

    def __enter__(self) -> None:
        ann = _ANNOTATION or _annotation()
        if ann is not None:
            ann = ann(self._name)
            ann.__enter__()
        self._ann = ann
        self._span = _trace.span(self._name)  # no-op unless armed
        self._span.__enter__()
        # the wall clock's reads enclose the CPU clock's, so a stage
        # that never waits reads CPU <= wall
        self._t0 = time.perf_counter()
        if self._cpu_name is not None:
            self._c0 = time.thread_time_ns()

    def __exit__(self, *exc) -> bool:
        cpu_s = (time.thread_time_ns() - self._c0) * 1e-9 \
            if self._cpu_name is not None else 0.0
        elapsed = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._reg._record(self._name, elapsed, self._cpu_name, cpu_s)
        return False


class Registry:
    def __init__(self):
        self._lock = _locks.new_lock("metrics.registry")
        self._counters: Dict[str, int] = {}
        self._timers: Dict[str, _Timer] = {}
        # timer observations wait here, appended without the lock (one
        # atomic deque append), so a collector callback that runs while
        # its own thread holds the lock never waits on it: the appender
        # that fills a batch folds it in when the lock is free, and
        # every read folds what is left
        self._pending: "collections.deque" = collections.deque()
        # per ``cpu=True`` stage name: executions so far, for sampling
        self._cpu_seq: Dict[str, Iterator[int]] = {}

    def count(self, name: str, n: int = 1) -> int:
        """Increment a counter; returns the new value."""
        with self._lock:
            v = self._counters.get(name, 0) + n
            self._counters[name] = v
            return v

    def counter(self, name: str) -> int:
        """One counter's current value (0 when never incremented) — a
        cheap single-name read for telemetry consumers (the worker
        heartbeat, the profiler's wide events) that must not pay a
        whole-registry snapshot copy per read."""
        with self._lock:
            return self._counters.get(name, 0)

    def _timer(self, name: str) -> _Timer:
        """The named histogram, created on first use."""
        t = self._timers.get(name)
        if t is None:
            # every caller holds self._lock
            t = self._timers[name] = _Timer()  # lint: ignore[LD001]
        return t

    def _record(self, name: str, elapsed_s: float,
                cpu_name: "str | None" = None, cpu_s: float = 0.0) -> None:
        """Queue one stage's wall time and, with ``cpu_name``, its
        thread CPU time, as one entry (folded under one acquisition:
        ``cpu_name`` gets the CPU time, ``<cpu_name>_wall`` the wall)."""
        pending = self._pending
        pending.append((name, elapsed_s, cpu_name, cpu_s))
        if len(pending) >= _FOLD_AT and self._lock.acquire(blocking=False):
            try:
                self._fold()
            finally:
                self._lock.release()

    def timer(self, name: str, cpu: bool = False) -> _Stage:
        """Context manager timing a stage as ``name``. With ``cpu``, the
        first of every ``CPU_SAMPLE_EVERY`` executions of the stage also
        records the thread's CPU time over it as ``<name>.cpu`` and its
        wall time again as ``<name>.cpu_wall``: their ratio over the
        sample stands for every execution."""
        if cpu:
            seq = self._cpu_seq.get(name)
            if seq is None:
                seq = self._cpu_seq.setdefault(name, itertools.count())
            cpu = next(seq) % CPU_SAMPLE_EVERY == 0
        return _Stage(self, name, cpu)

    def observe(self, name: str, elapsed_s: float) -> None:
        """Record a duration measured externally (never waits on the
        lock, so a collector callback may call it)."""
        self._record(name, elapsed_s)

    def _fold(self) -> None:
        """Move queued observations into their histograms (the caller
        holds the lock)."""
        pending = self._pending
        while pending:
            name, elapsed_s, cpu_name, cpu_s = pending.popleft()
            self._timer(name).add(elapsed_s)
            if cpu_name is not None:
                self._timer(cpu_name).add(cpu_s)
                self._timer(cpu_name + "_wall").add(elapsed_s)

    def snapshot(self) -> dict:
        """{"counters": {...}, "timers": {name: {count, total_s, mean_s,
        max_s, p50_s, p95_s, p99_s}}} — raw floats (see module doc)."""
        with self._lock:
            self._fold()
            counters = dict(self._counters)
            timers = {
                name: {
                    "count": t.count,
                    "total_s": t.total_s,
                    "mean_s": t.total_s / t.count if t.count else 0.0,
                    "max_s": t.max_s,
                    "p50_s": t.quantile(0.50),
                    "p95_s": t.quantile(0.95),
                    "p99_s": t.quantile(0.99),
                }
                for name, t in self._timers.items()
            }
        return {"counters": counters, "timers": timers}

    def export_state(self) -> Tuple[Dict[str, int],
                                    Dict[str, Tuple[int, float, float,
                                                    List[int]]]]:
        """One atomic copy for exposition writers: (counters,
        {timer: (count, total_s, max_s, bucket counts)}). Bucket counts
        align with ``BUCKET_BOUNDS_S`` plus one trailing overflow."""
        with self._lock:
            self._fold()
            counters = dict(self._counters)
            timers = {name: (t.count, t.total_s, t.max_s,
                             list(t.buckets))
                      for name, t in self._timers.items()}
        return counters, timers

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._pending.clear()

    def reset_timers(self) -> None:
        """Clear timers only: bench legs isolate one stage's histogram
        without zeroing cache-hit/egress counters mid-run."""
        with self._lock:
            self._timers.clear()
            self._pending.clear()


def snapshot_rounded(registry: "Registry | None" = None,
                     ndigits: int = 9) -> dict:
    """The /stats wire form: :meth:`Registry.snapshot` with timer floats
    rounded for the JSON body. 9 decimals = nanosecond resolution, so
    sub-microsecond stages stay visible (the old 6-decimal rounding
    inside snapshot() flattened them to 0.0)."""
    snap = (registry if registry is not None else default).snapshot()
    snap["timers"] = {
        name: {k: round(v, ndigits) if isinstance(v, float) else v
               for k, v in t.items()}
        for name, t in snap["timers"].items()}
    return snap


#: process-global registry used by the service/worker/pipeline
default = Registry()
count = default.count
counter = default.counter
timer = default.timer
observe = default.observe
snapshot = default.snapshot

# fork safety: a forked worker's /metrics must report ITS work, not a
# copy-on-write snapshot of the parent's (per-process metrics contract,
# README "Serving") — the child's default registry starts empty
from . import forksafe as _forksafe  # noqa: E402

_forksafe.register(default.reset)


def name_os_thread(name: str) -> None:
    """Give the calling thread ``name`` at the OS level (Linux, at most
    15 bytes), where the profiler reads it: the thread's host line in a
    profiler trace then carries this name, not the process's (or that
    of the thread that started it)."""
    try:
        with open("/proc/thread-self/comm", "w") as f:
            f.write(name[:15])
    except OSError:
        pass


#: when the open collector pass started, and its open annotation
_gc_t0 = 0.0
_gc_ann = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``process.gc.pause`` observation per
    pass of any generation, inside a ``process.gc`` annotation. Passes
    never overlap (the collector is not re-entered), so module state
    holds the open one. The pass may have started while this thread
    held the registry lock: ``observe`` never waits on it."""
    global _gc_t0, _gc_ann
    if phase == "start":
        ann = _annotation()
        if ann is not None:
            ann = ann("process.gc")
            ann.__enter__()
        _gc_ann = ann
        _gc_t0 = time.perf_counter()
        return
    elapsed = time.perf_counter() - _gc_t0
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    default.observe("process.gc.pause", elapsed)


def install_gc_timer() -> None:
    """Time every collector pass of this process into the default
    registry (idempotent; a forked child inherits the hook)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


@contextlib.contextmanager
def device_trace(out_dir: str) -> Iterator[None]:
    """Capture an XLA/TPU profiler trace into ``out_dir`` (view with
    TensorBoard's profile plugin or Perfetto). A no-op context if jax is
    unavailable. Every stage timer inside the region lands in the trace
    as an annotation on its thread's host line."""
    try:
        import jax
    except ImportError:  # pragma: no cover - jax is baked into this image
        yield
        return
    with jax.profiler.trace(out_dir):
        yield
