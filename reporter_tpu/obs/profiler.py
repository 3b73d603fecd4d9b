"""Device-level profiling: compile telemetry, bucket-occupancy wide
events, and shadow-accuracy sampling.

PR 7 gave the pipeline request-level eyes (spans, histograms, the
flight recorder); the compute layer below it stayed dark. This module
is the device-facing half:

- **Compile telemetry.** Every decode dispatch runs under
  :func:`dispatch_span`, which attributes ``jax.monitoring`` backend-
  compile events to the dispatching shape ``(B, T, K, platform)``. A
  dispatch during which any compile fired is a *compile episode*:
  counted (``decode.compile.count``), timed (``decode.compile``), and
  — when the SAME shape compiles a second time — flagged as a
  recompile storm (``decode.compile.recompiles`` + a log warning: a
  steady-state service recompiling a known shape is losing whole
  seconds to XLA, usually a jit-cache eviction or a drifting aux
  input). Dispatch wall time splits into ``decode.dispatch.first``
  (episodes that paid a compile) and ``decode.dispatch.steady``.
- **Wide events.** One bounded ring of per-chunk records (the
  "everything about this chunk on one line" discipline): bucket T, K,
  real traces vs padded rows, kept points vs padded ``rows*T`` point
  cells, the padding-waste ratio the fixed LENGTH_BUCKETS pay (the
  number that decides bucket tuning and the FLASH variable-length
  work), queue depth at dispatch, route-memo/cache hit snapshots, and
  the PR 7 ``trace_id`` when tracing is armed — so a slow traced
  request joins to the exact chunks that served it. Served by the
  service's ``/profile`` action; per-bucket occupancy histograms ride
  the metrics registry (``decode.occupancy.t<T>``) onto ``/stats``
  and ``/metrics``.
- **Shadow-accuracy sampling.** ``REPORTER_TPU_SHADOW_SAMPLE=0.05``
  re-decodes ~5% of chunks through the numpy oracle
  (matcher/cpu_ref.py) on ONE background thread, off the hot path, and
  compares *path quality* (f64 re-score — the device and the oracle
  may break exact score ties differently, which is agreement, not
  error). ``decode.shadow.{sampled,mismatch}`` counters export the
  verdicts; the per-chunk mismatch ratio lands in the
  ``decode.shadow.mismatch_ratio`` histogram so a
  ``REPORTER_TPU_SLO_MS`` budget on it flips ``/health`` 503 through
  the PR 7 machinery (the ratio rides the timer histogram: a budget of
  ``1000`` "ms" = ratio 1.0).

Cost discipline: chunk accounting is per *chunk* (hundreds of traces),
not per trace — a handful of scalar ops and one deque append. The
compile listener registers once, lazily, on the first dispatch.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils import locks as _locks
from ..utils import metrics
from ..utils.runtime import _env_float, _env_int
from . import trace as obs_trace

logger = logging.getLogger("reporter_tpu.obs")

ENV_SHADOW = "REPORTER_TPU_SHADOW_SAMPLE"
ENV_RING = "REPORTER_TPU_PROFILE_EVENTS"

#: score agreement tolerance for the shadow oracle, in f64 log-score
#: units — the same bound the device/oracle equivalence tests use
#: (ties may break differently; equal-quality paths are agreement)
SHADOW_SCORE_TOL = 1e-2

#: shadow chunks allowed in flight before sampling sheds load (the
#: sampler must never become its own backlog)
_SHADOW_MAX_PENDING = 4

_lock = _locks.new_lock("profiler")

#: (B, T, K, platform) -> per-shape stats dict (see dispatch_span)
_shapes: Dict[Tuple[int, int, int, str], dict] = {}

#: the wide-event ring; writes AND reads hold _lock (iterating a deque
#: mid-append raises), audited by the Guarded wrapper (racecheck RC003).
#: Sized once from the env at import, resizable via reset() for tests.
_events = _locks.Guarded(
    collections.deque(maxlen=max(16, _env_int(ENV_RING, 512))),
    _lock, "profiler.events")

_tls = threading.local()  # .active: [compile_calls, compile_s] or None

_listener_registered = False
_platform_cache: Optional[str] = None
#: per-dispatcher queue-depth gauges, keyed by dispatcher name. A
#: process can run several dispatchers (city stacks, tests); one
#: last-writer-wins scalar made them overwrite each other, and a
#: pre-fork child inherited the parent's stale depth — the registry is
#: cleared by the forksafe hook below so each worker gauges ITS queues
_queue_depths: Dict[str, int] = {}
_total_kept = 0           # running occupancy totals (point slots)
_total_cells = 0
_compile_episodes = 0

_shadow_acc = 0.0         # deterministic sampling accumulator
_shadow_pending = 0
_shadow_pool: Optional[ThreadPoolExecutor] = None
_shadow_sampled = 0
_shadow_mismatch = 0
#: pressure-ladder rung (service/admission.py "shed_shadow"): sampling
#: suspended under sustained overload — the oracle thread's CPU goes
#: back to serving. Suspensions are counted, never silent.
_shadow_suspended = False


# ---- compile telemetry -----------------------------------------------------

def _on_event_duration(name: str, dur_s: float, **_kw) -> None:
    """jax.monitoring listener: credit backend compiles to whichever
    dispatch is active on this thread (compilation is synchronous in
    the dispatching thread, so thread-local attribution is exact)."""
    if not name.endswith("backend_compile_duration"):
        return
    acc = getattr(_tls, "active", None)
    if acc is not None:
        acc[0] += 1
        acc[1] += dur_s


def _ensure_listener() -> None:
    global _listener_registered
    if _listener_registered:
        return
    with _lock:
        if _listener_registered:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _listener_registered = True


def _platform() -> str:
    global _platform_cache
    if _platform_cache is None:
        try:
            import jax
            p = jax.default_backend()
        except Exception:  # pragma: no cover
            p = "unknown"
        with _lock:
            _platform_cache = p
    return _platform_cache


class _DispatchSpan:
    """Times one decode dispatch and attributes compile events to its
    shape; updates the shape table and the decode.* metrics on exit."""

    __slots__ = ("B", "T", "K", "wire", "_acc", "_t0")

    def __init__(self, B: int, T: int, K: int, wire: str):
        self.B = B
        self.T = T
        self.K = K
        self.wire = wire

    def __enter__(self):
        _ensure_listener()
        self._acc = [0, 0.0]
        _tls.active = self._acc
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        elapsed = time.perf_counter() - self._t0
        _tls.active = None
        if exc_type is not None:
            # an aborted dispatch's wall is time-to-failure, not
            # latency: recording it would pollute the steady-state
            # histograms and seed shape entries with failure timings
            return False
        calls, compile_s = self._acc
        compiled = calls > 0
        # the backend, the mesh width AND the wire dtype are part of the
        # compiled-shape identity: switching REPORTER_TPU_DECODE (bench's
        # pallas leg, an operator A/B), a (B, T, K) that recompiles
        # because the decode mesh changed (device slice, DECODE_SHARD
        # flip), or a chunk whose distances overflow the f16 wire
        # (f32 inputs) is a new shape, not a recompile storm
        try:
            from ..ops import decode_backend, shard_width
            backend = decode_backend(self.T, self.K)
            mesh = shard_width(self.B, self.T, backend)
        except Exception:  # pragma: no cover - ops is always importable
            backend, mesh = "?", 1
        key = (self.B, self.T, self.K, _platform(), backend, mesh,
               self.wire)
        global _compile_episodes
        with _lock:
            st = _shapes.get(key)
            if st is None:
                st = _shapes[key] = {
                    "B": self.B, "T": self.T, "K": self.K,
                    "platform": key[3], "backend": backend,
                    "mesh": mesh, "wire": self.wire,
                    "dispatches": 0, "compiles": 0,
                    "compile_calls": 0, "compile_s": 0.0,
                    "first_s": elapsed, "steady_n": 0,
                    "steady_total_s": 0.0, "steady_max_s": 0.0}
            st["dispatches"] += 1
            recompiled = False
            if compiled:
                recompiled = st["compiles"] >= 1
                st["compiles"] += 1
                st["compile_calls"] += calls
                st["compile_s"] += compile_s
                _compile_episodes += 1
            else:
                st["steady_n"] += 1
                st["steady_total_s"] += elapsed
                if elapsed > st["steady_max_s"]:
                    st["steady_max_s"] = elapsed
        # metrics outside the lock (the registry has its own)
        if compiled:
            metrics.count("decode.compile.count")
            metrics.observe("decode.compile", compile_s)
            metrics.observe("decode.dispatch.first", elapsed)
            if recompiled:
                metrics.count("decode.compile.recompiles")
                logger.warning(
                    "recompile storm: decode shape B=%d T=%d K=%d "
                    "(%s/%s mesh=%d) compiled again (%d episodes, "
                    "%.0f ms this time) — a steady-state service "
                    "should compile each shape once", self.B, self.T,
                    self.K, key[3], backend, mesh, st["compiles"],
                    compile_s * 1e3)
        else:
            metrics.observe("decode.dispatch.steady", elapsed)
        return False


def dispatch_span(B: int, T: int, K: int,
                  wire: str = "float32") -> _DispatchSpan:
    """Wrap one decode dispatch (the matcher's dispatch lane); ``wire``
    is the decode inputs' dtype (f16 and f32 compile separately)."""
    return _DispatchSpan(B, T, K, wire)


# ---- wide events -----------------------------------------------------------

def note_queue_depth(depth: int, name: str = "dispatch") -> None:
    """Dispatcher backlog after draining a batch, per NAMED dispatcher
    — sampled into each wide event as "queue depth at dispatch"."""
    with _lock:
        _queue_depths[name] = int(depth)


def queue_depth(name: Optional[str] = None) -> int:
    """One dispatcher's last-noted depth, or — with no name — the max
    across every registered gauge (the wide events' scalar: the worst
    backlog is the one that matters under pressure)."""
    with _lock:
        if name is not None:
            return _queue_depths.get(name, 0)
        return max(_queue_depths.values(), default=0)


def queue_depths() -> Dict[str, int]:
    """Every named gauge (the /profile per-dispatcher view)."""
    with _lock:
        return dict(_queue_depths)


def _reset_queue_depths() -> None:
    """Forksafe hook: a pre-fork child starts with an empty gauge
    registry — the parent's dispatcher depths describe queues the
    child does not own (its own dispatchers re-note after their first
    drain)."""
    with _lock:
        _queue_depths.clear()


def chunk_event(bucket_T: int, K: int, traces: int, rows: int,
                kept_points: int, raw_points: int,
                cache: Optional[dict] = None,
                path: str = "native") -> None:
    """Record one decode chunk's wide event (called once per chunk by
    the matcher's dispatch paths — a handful of scalars, one append).

    ``rows`` is the padded batch dimension (mesh/pow2 filler included),
    so ``rows * bucket_T`` is the point-slot grid the device actually
    decodes; ``kept_points`` is how many of those slots carry a real
    (kept) probe point. The waste ratio is what adaptive/variable
    bucketing (FLASH) would reclaim.
    """
    # the ONE occupancy formula, shared with the pinning tests (lazy
    # import: batchpad sits under matcher/, which imports this module)
    from ..matcher.batchpad import occupancy_stats
    global _total_kept, _total_cells
    cells, occupancy, waste = occupancy_stats(kept_points, rows,
                                              bucket_T)
    ctx = obs_trace.current()
    event = {
        "ts_ms": int(time.time() * 1000),
        "trace_id": ctx[0] if ctx is not None else None,
        "path": path,
        "bucket_T": int(bucket_T),
        "K": int(K),
        "traces": int(traces),
        "rows": int(rows),
        "raw_points": int(raw_points),
        "kept_points": int(kept_points),
        "padded_cells": int(cells),
        "occupancy": round(occupancy, 6),
        "padding_waste": round(waste, 6),
        "queue_depth": queue_depth(),
    }
    if cache:
        event["cache"] = cache
    with _lock:
        # ring writes AND reads hold the lock: a lone deque append is
        # atomic, but iterating a deque raises RuntimeError when a
        # concurrent append lands mid-iteration — and recent_events()
        # feeds both /profile and the flight-recorder crash dump.
        # (extend, not append: the lockgraph pass resolves bare-name
        # calls package-wide, and `append` under a lock reads as
        # HistogramStore.append — a builtin deque method is invisible
        # to it either way, so use the spelling with no collision)
        _events.extend((event,))
        _total_kept += int(kept_points)
        _total_cells += int(cells)
    metrics.count("profile.chunks")
    # per-bucket occupancy histogram: the ratio rides the fixed
    # log-bucket timer machinery (units are ratio, not seconds) so
    # /stats gets p50/p95/p99 occupancy per bucket and /metrics a
    # scrapeable histogram family per bucket
    metrics.observe(f"decode.occupancy.t{int(bucket_T)}", occupancy)


def recent_events(n: Optional[int] = 16) -> List[dict]:
    """The last ``n`` wide events, oldest first (a snapshot copy).
    ``n=0`` means none, ``None`` means the whole ring."""
    with _lock:
        evs = list(_events)
    if n is None:
        return evs
    return evs[-n:] if n > 0 else []


def padding_waste() -> Optional[float]:
    """Lifetime padding-waste ratio across every recorded chunk; None
    before the first chunk."""
    with _lock:
        if not _total_cells:
            return None
        return 1.0 - _total_kept / _total_cells


def compile_count() -> int:
    with _lock:
        return _compile_episodes


# ---- shadow-accuracy sampling ----------------------------------------------

def shadow_fraction() -> float:
    return max(0.0, _env_float(ENV_SHADOW, 0.0))


def set_shadow_suspended(on: bool) -> None:
    """Pressure-ladder rung (service/admission.py): suspend / resume
    shadow-accuracy sampling. Under the lock only for write-discipline
    consistency with reset(); readers take one global load."""
    global _shadow_suspended
    with _lock:
        _shadow_suspended = bool(on)


def _ensure_shadow_pool() -> ThreadPoolExecutor:
    global _shadow_pool
    with _lock:
        if _shadow_pool is None:
            _shadow_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shadow-decode")
        return _shadow_pool


def maybe_shadow(batch, decoded: np.ndarray, n_real: int,
                 sigma: float, beta: float) -> None:
    """Sample this chunk for shadow decoding (deterministic accumulator
    — a fraction of 0.25 samples exactly every 4th chunk). The oracle
    runs on one background thread; when it falls behind, chunks are
    shed (counted) rather than queued without bound."""
    frac = shadow_fraction()
    if frac <= 0.0 or n_real <= 0:
        return
    if _shadow_suspended:
        # the shed_shadow pressure rung: sampling paused, accounted —
        # the accumulator does not advance, so easing pressure resumes
        # the configured cadence, not a burst of catch-up chunks
        metrics.count("decode.shadow.suppressed")
        return
    global _shadow_acc, _shadow_pending
    with _lock:
        _shadow_acc += min(frac, 1.0)
        if _shadow_acc < 1.0:
            return
        _shadow_acc -= 1.0
        if _shadow_pending >= _SHADOW_MAX_PENDING:
            shed = True
        else:
            shed = False
            _shadow_pending += 1
    if shed:
        metrics.count("decode.shadow.dropped")
        return
    try:
        pool = _ensure_shadow_pool()
        pool.submit(_shadow_job, batch.dist_m, batch.valid,
                    batch.route_m, batch.gc_m, batch.case,
                    np.asarray(decoded), n_real, float(sigma),
                    float(beta))
    except Exception as e:
        # submit itself can fail (thread exhaustion, interpreter
        # shutdown); the sampler must never take down serving, and the
        # reserved pending slot must not leak (4 leaks would shed every
        # future chunk and hang drain_shadow)
        with _lock:
            _shadow_pending -= 1
        metrics.count("decode.shadow.errors")
        logger.error("shadow submit failed (chunk skipped): %s", e)


def _path_score_f64(dist_row, route_row, gc_row, case_row, path,
                    sigma: float, beta: float, n: int,
                    normal_code: int, unreachable: float) -> float:
    """Re-score a decoded path in f64, independent of either decoder's
    accumulation order (vectorised twin of the equivalence tests'
    scorer). Returns -inf when the path crosses an unroutable
    transition — always a mismatch."""
    if n <= 0:
        return 0.0
    p = np.asarray(path[:n], dtype=np.int64)
    d = dist_row[np.arange(n), p].astype(np.float64)
    total = float((-0.5 * (d / sigma) ** 2).sum())
    if n > 1:
        steps = np.arange(1, n)
        normal = np.asarray(case_row[1:n]) == normal_code
        r = route_row[steps - 1, p[:-1], p[1:]].astype(np.float64)
        if bool((r[normal] >= unreachable).any()):
            return float("-inf")
        dev = np.abs(r - np.asarray(gc_row[:n - 1], dtype=np.float64))
        total += float(np.where(normal, -dev / beta, 0.0).sum())
    return total


def _shadow_job(dist, valid, route, gc, case, decoded, n_real: int,
                sigma: float, beta: float) -> None:
    global _shadow_sampled, _shadow_mismatch, _shadow_pending
    try:
        # lazy: cpu_ref sits under matcher/, which imports this module
        from ..matcher.cpu_ref import viterbi_decode_numpy_batch
        from ..matcher.hmm import NORMAL, SKIP, UNREACHABLE_THRESHOLD
        # host copies, here off the hot path: a device-route chunk's
        # route tensor is still a device array, and indexing it per
        # trace would run (and compile) device ops per shape
        dist, valid, route, gc = (np.asarray(a)[:n_real] for a in
                                  (dist, valid, route, gc))
        case = np.asarray(case)[:n_real]
        T = dist.shape[1]
        # native batches carry a dead trailing time row (seq sharding);
        # the oracle's contract is (T-1, K, K)
        route = route[:, :max(T - 1, 0)]
        gc = gc[:, :max(T - 1, 0)]
        # the whole chunk in one row-vectorised oracle pass (per-row
        # identical to the per-trace oracle): a per-trace loop could not
        # keep up with chunks decoded on a chip
        oracle_paths, _ = viterbi_decode_numpy_batch(
            dist, valid, route, gc, case, sigma, beta)
        mismatches = 0
        for b in range(n_real):
            n = int(np.count_nonzero(case[b] != SKIP))
            if n == 0:
                continue
            s_dev = _path_score_f64(dist[b], route[b], gc[b], case[b],
                                    decoded[b], sigma, beta, n, NORMAL,
                                    UNREACHABLE_THRESHOLD)
            s_np = _path_score_f64(dist[b], route[b], gc[b], case[b],
                                   oracle_paths[b], sigma, beta, n, NORMAL,
                                   UNREACHABLE_THRESHOLD)
            # path QUALITY comparison: a differently-broken exact tie
            # is agreement; a worse-scoring device path is the bug the
            # sampler exists to catch
            if not (abs(s_dev - s_np) <= SHADOW_SCORE_TOL):
                mismatches += 1
        metrics.count("decode.shadow.chunks")
        metrics.count("decode.shadow.sampled", n_real)
        if mismatches:
            metrics.count("decode.shadow.mismatch", mismatches)
            logger.warning(
                "shadow decode: %d/%d traces in a sampled chunk scored "
                "differently from the numpy oracle", mismatches, n_real)
        metrics.observe("decode.shadow.mismatch_ratio",
                        mismatches / n_real)
        with _lock:
            _shadow_sampled += n_real
            _shadow_mismatch += mismatches
    except Exception as e:  # the sampler must never take down serving
        metrics.count("decode.shadow.errors")
        logger.error("shadow decode failed (chunk skipped): %s", e)
    finally:
        with _lock:
            _shadow_pending -= 1


def shadow_stats() -> dict:
    with _lock:
        return {"fraction": shadow_fraction(),
                "sampled": _shadow_sampled,
                "mismatch": _shadow_mismatch,
                "pending": _shadow_pending,
                "suspended": _shadow_suspended}


def shadow_mismatches() -> int:
    with _lock:
        return _shadow_mismatch


def drain_shadow(timeout_s: float = 30.0) -> bool:
    """Block until no shadow chunk is in flight (tests / smoke gates);
    True when drained, False on timeout."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with _lock:
            if _shadow_pending == 0:
                return True
        time.sleep(0.005)
    return False


def shutdown_shadow_pool(timeout_s: float = 30.0) -> bool:
    """Drain in-flight shadow chunks, then JOIN the sampler thread —
    the worker's shutdown-ordering contract (ISSUE 10): no oracle job
    may outlive the spool/datastore handles the final flush is about to
    release. A later :func:`maybe_shadow` lazily recreates the pool
    (multi-worker processes share it). True when the drain completed."""
    global _shadow_pool
    drained = drain_shadow(timeout_s)
    with _lock:
        pool, _shadow_pool = _shadow_pool, None
    if pool is not None:
        pool.shutdown(wait=True)
    return drained


# ---- export ----------------------------------------------------------------

def _shape_view(st: dict) -> dict:
    """One shape-table row as the /profile wire form (first-call vs
    steady-state split folded into a ``steady`` sub-object)."""
    n = st["steady_n"]
    return {
        "B": st["B"], "T": st["T"], "K": st["K"],
        "platform": st["platform"],
        "backend": st["backend"],
        "mesh": st.get("mesh", 1),
        "wire": st.get("wire"),
        "dispatches": st["dispatches"],
        "compiles": st["compiles"],
        "compile_calls": st["compile_calls"],
        "compile_s": round(st["compile_s"], 6),
        "first_s": round(st["first_s"], 6),
        "steady": {"n": n,
                   "mean_s": round(st["steady_total_s"] / n, 6)
                   if n else 0.0,
                   "max_s": round(st["steady_max_s"], 6)},
    }


def snapshot(n_events: int = 64) -> dict:
    """The ``/profile`` payload: per-shape compile/dispatch stats, the
    last ``n_events`` wide events, lifetime occupancy totals, shadow
    verdicts, and the last-seen dispatcher queue depth."""
    with _lock:
        raw = [dict(st) for st in _shapes.values()]
        kept, cells = _total_kept, _total_cells
        depths = dict(_queue_depths)
        episodes = _compile_episodes
    shapes = [_shape_view(st) for st in raw]
    shapes.sort(key=lambda s: (s["T"], s["K"], s["B"]))
    return {
        "shapes": shapes,
        "compile_episodes": episodes,
        "events": recent_events(n_events),
        "totals": {
            "kept_points": kept,
            "padded_cells": cells,
            "padding_waste": round(1.0 - kept / cells, 6) if cells
            else None},
        "shadow": shadow_stats(),
        "routes": route_kernel_stats(),
        "queue_depth": max(depths.values(), default=0),
        "queue_depths": depths,
    }


def route_kernel_stats() -> dict:
    """Device-vs-host route-stage split for ``/profile``: chunks the
    device kernel served vs chunks that fell back to (or never left)
    the host Dijkstra path, so a prep_routes regression is attributable
    at a glance — a sick device shows up as fallback/error counts, a
    disabled knob as device_chunks == 0."""
    from ..utils import metrics
    c = metrics.default.counter
    return {
        "device_chunks": c("route.device.chunks"),
        "device_pairs": c("route.device.pairs"),
        "device_sources": c("route.device.sources"),
        "sharded_chunks": c("route.device.sharded_chunks"),
        "deferred_chunks": c("route.device.deferred_chunks"),
        "async_dispatch_chunks": c("route.device.async_dispatch_chunks"),
        "cache_hit_rows": c("route.device.cache_hit_rows"),
        "cache_miss_rows": c("route.device.cache_miss_rows"),
        "empty_chunks": c("route.device.empty_chunks"),
        "fallback_chunks": c("route.device.fallback_chunks"),
        "circuit_skipped_chunks": c("route.device.circuit_skipped_chunks"),
        "errors": c("route.device.errors"),
    }


def reset() -> None:
    """Drop every table/ring/total (tests). Re-reads the ring-size env
    so a test can shrink the ring."""
    global _total_kept, _total_cells, _compile_episodes, \
        _shadow_acc, _shadow_pending, _shadow_sampled, _shadow_mismatch, \
        _shadow_suspended, _events
    with _lock:
        _shapes.clear()
        _queue_depths.clear()
        _shadow_suspended = False
        _total_kept = 0
        _total_cells = 0
        _compile_episodes = 0
        _shadow_acc = 0.0
        _shadow_pending = 0
        _shadow_sampled = 0
        _shadow_mismatch = 0
        _events = _locks.Guarded(
            collections.deque(maxlen=max(16, _env_int(ENV_RING, 512))),
            _lock, "profiler.events")


# fork safety: a pre-fork child must never inherit the parent's
# dispatcher queue-depth gauges (they describe queues the child does
# not own; its own dispatchers re-note after their first drain)
from ..utils import forksafe as _forksafe  # noqa: E402

_forksafe.register(_reset_queue_depths)
