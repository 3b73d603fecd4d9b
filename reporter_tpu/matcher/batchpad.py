"""Host-side trace preparation: candidates, route tensors, padding buckets.

Two pieces of irregularity are resolved here so the device program stays
fixed-shape and branch-free (SURVEY.md §7 "Hard parts: raggedness"):

1. **Point filtering.** Probe points closer than ``interpolation_distance``
   to the last kept point (GPS jitter while slow/stopped) and points with no
   candidate edges are *excluded* from the HMM; the Viterbi runs over the
   kept subsequence only, and excluded jitter points are attributed to the
   decoded runs afterwards (candidate-less probes — off-network — stay
   unattributed wherever they occur; see assemble.py's span fix-up). This
   mirrors Meili's interpolation behavior and is what keeps
   backward-jitter from reading as a u-turn.

2. **Bucketed padding.** Kept subsequences are padded to the smallest bucket
   in ``LENGTH_BUCKETS`` so XLA compiles a handful of shapes, not thousands.
"""
from __future__ import annotations

import bisect
import os as _os
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

from ..core.geo import equirectangular_m
from ..core.tracebatch import TraceBatch, points_to_columns
from ..graph.network import RoadNetwork
from ..graph.route import RouteCache, candidate_route_matrices, UNREACHABLE
from ..graph.spatial import CandidateSet, SpatialGrid, PAD_EDGE, PAD_DIST
from .hmm import (
    NORMAL, RESTART, SKIP, UNREACHABLE_THRESHOLD, WIRE_MAX_M)
from .params import MatchParams

LENGTH_BUCKETS = (16, 64, 256, 1024)

#: FLASH-style candidate pruning margin, in multiples of the HMM's
#: effective sigma: after the distance-sorted candidate gather, a
#: point's candidates beyond ``dist[0] + sigma_mult * effective_sigma``
#: are dropped BEFORE any route between them is requested — their
#: emission probability is already vanishing relative to the best
#: candidate, so the route columns they'd occupy are near-certain
#: Viterbi losers. 0 (default) disables pruning; the shadow-accuracy
#: sampler (obs/shadow.py) is the guard rail when arming it.
ENV_PRUNE = "REPORTER_TPU_ROUTE_PRUNE_SIGMA"

#: runtime bucket-ladder override: "16,64,256,1024" (ascending ints),
#: with an optional "@<waste>" suffix setting the occupancy-driven
#: split threshold ("@1" / "@off" disables splitting). Default: the
#: fixed LENGTH_BUCKETS ladder with splitting at DEFAULT_SPLIT_WASTE.
ENV_BUCKETS = "REPORTER_TPU_BUCKETS"

#: padding-waste ratio above which the native dispatcher breaks a
#: mixed-length chunk into per-pow2-bucket sub-batches (matcher.py
#: SegmentMatcher._split_bucket) — high enough that the exact-fill steady state
#: (BENCH_DEV_r07 recorded 0.21 whole-run, mostly jitter drops and
#: pow2 row padding a finer T can't reclaim) never splits, low enough
#: that a 17-point trace padding to T=64 (waste ~0.73) always does
DEFAULT_SPLIT_WASTE = 0.35

_ladder_cache: "dict[str, tuple]" = {}

#: pressure-ladder rung (service/admission.py "coarse_buckets"): under
#: sustained overload the adaptive splitter is disabled — fewer, larger
#: decode shapes, no split dispatches and no fresh compile episodes
#: mid-storm. The ladder flips it; bucket_ladder() reports threshold
#: 1.0 (never split) while it holds.
_pressure_coarse = False


def set_pressure_coarse(on: bool) -> None:
    global _pressure_coarse
    _pressure_coarse = bool(on)


def bucket_ladder() -> "tuple[tuple, float]":
    """(ladder, split_threshold) from REPORTER_TPU_BUCKETS; the default
    fixed ladder with the default threshold when unset. A malformed
    spec logs and keeps the default (a typo'd ladder must degrade to
    the shipped shapes, never to an unbounded shape zoo).

    The native dispatcher's chunk plan (``SegmentMatcher._plan_chunks``)
    buckets a group on this ladder and splits a bucket past the
    threshold; then it merges a group of at most 128 traces into one
    chunk, at the power of two of its longest trace between
    ``ladder[0]`` and that trace's bucket (the bucket itself at
    threshold 1.0), where the padding that adds is worth less than the
    chunks it saves."""
    spec = _os.environ.get(ENV_BUCKETS, "").strip()
    if not spec:
        # the default is NOT cached: LENGTH_BUCKETS is read live, so
        # tests that monkeypatch the module ladder keep working
        return (LENGTH_BUCKETS,
                1.0 if _pressure_coarse else DEFAULT_SPLIT_WASTE)
    got = _ladder_cache.get(spec)
    if got is not None:
        return (got[0], 1.0) if _pressure_coarse else got
    ladder, thresh = LENGTH_BUCKETS, DEFAULT_SPLIT_WASTE
    if spec:
        body, _, tail = spec.partition("@")
        try:
            if tail.strip().lower() in ("off", "no", "false"):
                thresh = 1.0
            elif tail.strip():
                thresh = float(tail)
            vals = tuple(int(v) for v in body.split(",") if v.strip())
            if body.strip():
                if not vals or any(v <= 0 for v in vals) or \
                        list(vals) != sorted(set(vals)):
                    raise ValueError("ladder must be ascending positive")
                ladder = vals
            if not 0.0 < thresh:
                raise ValueError("threshold must be positive")
        except ValueError as e:
            import logging
            logging.getLogger("reporter_tpu.matcher").warning(
                "%s=%r not understood (%s); keeping the default ladder",
                ENV_BUCKETS, spec, e)
            ladder, thresh = LENGTH_BUCKETS, DEFAULT_SPLIT_WASTE
    _ladder_cache[spec] = (ladder, thresh)
    return (ladder, 1.0) if _pressure_coarse else (ladder, thresh)


def bucket_length(n: int) -> int:
    """Smallest bucket >= n (the last bucket caps the trace length).
    Reads the runtime ladder (REPORTER_TPU_BUCKETS; default unchanged)."""
    ladder, _ = bucket_ladder()
    idx = bisect.bisect_left(ladder, n)
    return ladder[min(idx, len(ladder) - 1)]


def kept_point_count(batch: "PaddedBatch") -> int:
    """Kept (non-SKIP) probe points across a padded batch — the
    occupancy numerator of the profiler's wide events. One whole-tensor
    count over the (B, T) case codes: pad rows and padding tails are
    all-SKIP by construction, so no per-trace view materialises."""
    return int(np.count_nonzero(np.asarray(batch.case) != SKIP))


def occupancy_stats(kept_points: int, rows: int, T: int
                    ) -> "tuple[int, float, float]":
    """(padded point cells, occupancy, padding-waste ratio) for a batch
    padded to ``rows`` traces of bucket length ``T``. The waste ratio
    is the fraction of decoded point slots that carry no real probe —
    what variable-length (FLASH-style) bucketing would reclaim; the
    candidate width K scales both sides, so it cancels."""
    cells = rows * T
    occ = kept_points / cells if cells else 0.0
    return cells, occ, 1.0 - occ


@dataclass
class PreparedTrace:
    """One trace's fixed-width tensors, padded to bucket length T.

    Tensor rows 0..num_kept-1 correspond to the *kept* points;
    ``kept_idx`` maps them back to indices in the original trace.
    """
    num_raw: int           # points in the original trace
    num_kept: int          # points included in the HMM
    kept_idx: np.ndarray   # (num_kept,) i32 original indices
    times: np.ndarray      # (num_raw,) f64 epoch seconds
    edge_ids: np.ndarray   # (T, K) i32
    dist_m: np.ndarray     # (T, K) f32
    offset_m: np.ndarray   # (T, K) f32
    route_m: np.ndarray    # (T-1, K, K) f32
    gc_m: np.ndarray       # (T-1,) f32
    case: np.ndarray       # (T,) i32
    # seconds the raw tail verifiably dwelt at the last kept point (jitter
    # drops only; 0 when the tail was off-network or bucket-truncated)
    trailing_jitter_dwell_s: float = 0.0
    # (num_raw,) u8/bool: raw point had any candidate edge; None on
    # hand-built preps (assembler then treats every drop as jitter)
    has_cands: "np.ndarray | None" = None

    @property
    def T(self) -> int:
        return self.edge_ids.shape[0]


def _select_kept(lat, lon, has_cands, interpolation_distance):
    """Indices of points that enter the HMM: drop candidate-less points and
    points within ``interpolation_distance`` of the last kept point.

    Vectorised common case: when every consecutive pair of candidate-
    bearing points is at least the interpolation distance apart (a moving
    vehicle — the overwhelming majority of traces), the anchor never
    skips a point and the answer is one array op. The sequential scan
    only runs from the first violation onward (a slow/stopped stretch),
    where the moving-anchor semantics are irreducibly order-dependent.
    """
    has = np.asarray(has_cands, dtype=bool)
    idx = np.flatnonzero(has)
    if idx.size <= 1:
        return idx.astype(np.int32)
    lat = np.asarray(lat)
    lon = np.asarray(lon)
    gc = np.atleast_1d(equirectangular_m(lat[idx[:-1]], lon[idx[:-1]],
                                         lat[idx[1:]], lon[idx[1:]]))
    viol = np.flatnonzero(gc < interpolation_distance)
    if viol.size == 0:
        return idx.astype(np.int32)
    j = int(viol[0])  # pairs before the first violation are all kept
    kept = idx[:j + 1].tolist()
    for i in idx[j + 1:].tolist():
        gc_i = equirectangular_m(lat[kept[-1]], lon[kept[-1]],
                                 lat[i], lon[i])
        if gc_i < interpolation_distance:
            continue
        kept.append(i)
    return np.asarray(kept, dtype=np.int32)


def prepare_trace(net: RoadNetwork, grid: SpatialGrid | None,
                  points: Sequence[dict], params: MatchParams,
                  cache: RouteCache | None = None,
                  runtime=None) -> PreparedTrace:
    """Candidates + route tensors + case codes for one trace, padded.

    ``runtime`` (reporter_tpu.native.NativeRuntime) supplies C++ candidate
    lookup and route matrices when available; the numpy ``grid`` + ``cache``
    path is the fallback with identical semantics. ``points`` is a point-
    dict sequence (converted to columns once, here at the edge).
    """
    lat, lon, times, _acc = points_to_columns(points)
    lookup = runtime if runtime is not None else grid
    all_cands = lookup.candidates(lat, lon, params.max_candidates,
                                  params.search_radius)
    has_cands = (all_cands.edge_ids != PAD_EDGE).any(axis=1)
    return _prepare_from_candidates(net, lat, lon, times, all_cands,
                                    has_cands, params, cache, runtime)


def prepare_traces_numpy(net: RoadNetwork, grid: SpatialGrid,
                         tb: TraceBatch, params: MatchParams,
                         cache: RouteCache | None = None,
                         ) -> List[PreparedTrace]:
    """Whole-chunk numpy host prep (the fallback hot path): ONE vectorised
    candidate search over every point of every trace in the chunk, then
    per-trace route tensors through the shared cross-batch route cache.
    Same per-trace semantics as :func:`prepare_trace` — the candidate
    tensors sliced out of the batch lookup are identical to a per-trace
    lookup because the grid query is a pure per-point function."""
    K = params.max_candidates
    all_c = grid.candidates(tb.lat, tb.lon, K, params.search_radius)
    has_all = (all_c.edge_ids != PAD_EDGE).any(axis=1)
    out = []
    offsets = tb.offsets
    for b in range(len(tb)):
        lo, hi = int(offsets[b]), int(offsets[b + 1])
        sub = CandidateSet(
            edge_ids=all_c.edge_ids[lo:hi], dist_m=all_c.dist_m[lo:hi],
            offset_m=all_c.offset_m[lo:hi], proj_x=all_c.proj_x[lo:hi],
            proj_y=all_c.proj_y[lo:hi])
        out.append(_prepare_from_candidates(
            net, tb.lat[lo:hi], tb.lon[lo:hi], tb.time[lo:hi], sub,
            has_all[lo:hi], params, cache, None))
    return out


def _prepare_from_candidates(net, lat, lon, times, all_cands, has_cands,
                             params: MatchParams, cache, runtime
                             ) -> PreparedTrace:
    """Kept-point selection, route tensors, case codes and padding for one
    trace whose candidate lookup already happened (shared by the
    per-trace and whole-batch prep paths)."""
    num_raw = len(lat)
    K = params.max_candidates
    kept = _select_kept(lat, lon, has_cands, params.interpolation_distance)
    n = len(kept)
    T = bucket_length(max(n, 1))
    truncated = n > T
    if truncated:  # cap at the largest bucket
        kept = kept[:T]
        n = T

    # dwell time of a *jitter-only* trailing tail: every raw point after the
    # last kept one must have candidates and sit within the interpolation
    # distance of that kept point — i.e. the vehicle verifiably stayed put.
    # Tails dropped for lacking candidates (off-network driving) or by
    # bucket truncation carry no such guarantee and count no dwell. Used by
    # segment assembly to detect a vehicle queued at trace end.
    trailing_jitter_dwell_s = 0.0
    if n and not truncated and int(kept[-1]) < num_raw - 1:
        lk = int(kept[-1])
        tail = np.arange(lk + 1, num_raw)
        tail_gc = equirectangular_m(lat[lk], lon[lk], lat[tail], lon[tail])
        if bool(has_cands[tail].all()) and \
                bool((np.atleast_1d(tail_gc)
                      < params.interpolation_distance).all()):
            trailing_jitter_dwell_s = float(times[num_raw - 1] - times[lk])

    cands = CandidateSet(
        edge_ids=all_cands.edge_ids[kept], dist_m=all_cands.dist_m[kept],
        offset_m=all_cands.offset_m[kept], proj_x=all_cands.proj_x[kept],
        proj_y=all_cands.proj_y[kept])
    cands = _prune_candidates(cands, _route_prune_margin(params))

    gc = equirectangular_m(lat[kept[:-1]], lon[kept[:-1]],
                           lat[kept[1:]], lon[kept[1:]]) if n > 1 else np.zeros(0)
    gc = np.atleast_1d(np.asarray(gc, dtype=np.float32))

    # probe time deltas between consecutive KEPT points feed Meili's
    # max_route_time_factor admissibility bound (reference: Dockerfile:16);
    # None disables the bound entirely (factor <= 0)
    dt = None
    if params.max_route_time_factor > 0 and n > 1:
        dt = np.diff(times[kept])

    if runtime is not None:
        route = runtime.route_matrices(
            cands, gc,
            max_route_distance_factor=params.max_route_distance_factor,
            backward_tolerance_m=params.backward_tolerance_m,
            dt=dt, max_route_time_factor=params.max_route_time_factor,
            min_time_bound_s=params.min_time_bound_s,
            turn_penalty_factor=params.turn_penalty_factor)
    else:
        route = candidate_route_matrices(
            net, cands, gc,
            max_route_distance_factor=params.max_route_distance_factor,
            cache=cache,
            backward_tolerance_m=params.backward_tolerance_m,
            dt=dt, max_route_time_factor=params.max_route_time_factor,
            min_time_bound_s=params.min_time_bound_s,
            turn_penalty_factor=params.turn_penalty_factor)

    # case codes over kept points: RESTART at the first point and after
    # breakage-sized gaps; SKIP only in the padding tail
    case = np.full(T, SKIP, dtype=np.int32)
    if n:
        case[:n] = NORMAL
        case[0] = RESTART
        if n > 1:
            case[1:n][gc[:n - 1] > params.breakage_distance] = RESTART

    # pad to bucket
    edge_ids = np.full((T, K), PAD_EDGE, dtype=np.int32)
    dist = np.full((T, K), PAD_DIST, dtype=np.float32)
    offset = np.zeros((T, K), dtype=np.float32)
    route_p = np.full((max(T - 1, 0), K, K), UNREACHABLE, dtype=np.float32)
    gc_p = np.zeros(max(T - 1, 0), dtype=np.float32)

    edge_ids[:n] = cands.edge_ids
    dist[:n] = cands.dist_m
    offset[:n] = cands.offset_m
    if n > 1:
        route_p[:n - 1] = route
        gc_p[:n - 1] = gc

    return PreparedTrace(num_raw=num_raw, num_kept=n, kept_idx=kept,
                         times=times, edge_ids=edge_ids, dist_m=dist,
                         offset_m=offset, route_m=route_p, gc_m=gc_p,
                         case=case,
                         trailing_jitter_dwell_s=trailing_jitter_dwell_s,
                         has_cands=np.asarray(has_cands))


class _LazyTraceViews:
    """Sequence of PreparedTrace views built on first element access.

    The native hot path (SegmentMatcher._drain_stage with batched
    assembly) only ever needs ``len()`` — building 512 dataclass views
    with 8 numpy slices each cost ~3 ms per chunk for nothing. Tests
    and the fallback assembler index/iterate, which materialises."""

    def __init__(self, n: int, build):
        self._n = n
        self._build = build
        self._views: List[PreparedTrace] | None = None

    def _mat(self) -> List[PreparedTrace]:
        if self._views is None:
            self._views = self._build()
        return self._views

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        return self._mat()[i]

    def __iter__(self):
        return iter(self._mat())


@dataclass
class PaddedBatch:
    """A device-ready batch of same-bucket traces."""
    traces: "List[PreparedTrace] | _LazyTraceViews"
    dist_m: np.ndarray   # (B, T, K) f32
    valid: np.ndarray    # (B, T, K) bool
    # route/gc time rows: T-1 on the numpy pack_batches path, T on the
    # native prepare_batch path (dead trailing step so the dominant
    # tensor shards along seq with zero pad copies); the decode kernels
    # accept either and slice inside jit (matcher/hmm.py trim_time_pad)
    route_m: np.ndarray  # (B, T-1 | T, K, K) f32
    gc_m: np.ndarray     # (B, T-1 | T) f32
    case: np.ndarray     # (B, T) i32
    # native batched-prep extras (None on the per-trace fallback path):
    # the raw prepare_batch tensors + flat point arrays, consumed by the
    # native batched assembler (NativeRuntime.assemble_batch)
    prep: dict | None = None
    pt_off: np.ndarray | None = None     # (B+1,) i64
    times_flat: np.ndarray | None = None  # flat f64 raw probe times
    # deferred wire finalisation (the device-resident route path of
    # prepare_batch(defer_routes=True)): the decode stage runs it once
    # before reading the batch tensors, paying the device sync there —
    # overlapped with the next chunk's native prep — instead of in prep
    finalize: "object | None" = None

    def finalize_wire(self) -> None:
        """Run the deferred route write-back + wire-dtype cast; no-op
        when the batch was built synchronously."""
        f, self.finalize = self.finalize, None
        if f is not None:
            f(self)


def prepare_batch(runtime, traces_points: Sequence[Sequence[dict]],
                  params: MatchParams, T: int,
                  pad_rows: int | None = None,
                  n_threads: int = 0,
                  route_kernel=None,
                  route_circuit=None,
                  defer_routes: bool = False) -> PaddedBatch:
    """Whole-chunk host prep through ONE native call (the hot path).

    Same per-trace semantics as :func:`prepare_trace` — the C++ side
    (host_runtime.cpp rt_prepare_batch) mirrors candidate search, jitter/
    no-candidate selection, case codes and route bounds exactly, and the
    parity is pinned by tests/test_native.py — but with zero per-trace
    Python: one ctypes round-trip prepares the whole chunk straight into
    padded (B, T, ...) tensors, fanned out across C++ threads. This is
    what replaces the reference's one-C++-Match-per-trace architecture
    (reference: py/reporter_service.py:240) on the host side; BENCH_r03
    measured per-trace Python as the end-to-end ceiling.

    ``traces_points``: a columnar :class:`TraceBatch` (the zero-dict hot
    path — flat coordinate arrays pass straight through to the native
    call) or one list of point dicts per trace (converted here, once).
    ``T``: the padding bucket (all traces in a chunk share it — callers
    bucket by raw length first). ``pad_rows`` >= B adds all-SKIP filler
    rows (mesh divisibility / pow2 shape bounding). Float tensors ship on
    the f16 wire when every finite distance fits (same policy as
    pack_batches).

    ``route_kernel`` (graph/route_device.py DeviceRouteKernel) moves the
    route-cost stage onto the device: the native call runs with
    ``skip_routes`` and the kernel fills ``route_m`` from one batched
    bounded relaxation. Any device failure (or an open ``route_circuit``)
    falls back to a native re-prep WITH routes — byte-identical output,
    just slower — and records the outcome on the circuit so a sick
    device stops being retried per-chunk.

    ``defer_routes=True`` (the pipelined matcher's mode) keeps the
    device route tensor DEVICE-RESIDENT: the assembly is dispatched in
    prep but never synced here — ``route_m`` on the returned batch is
    the in-flight device array (padded to the native wire layout) and
    the batch carries a ``finalize`` closure the decode stage runs
    before reading tensors, which pays the sync + wire-f16 decision
    there, overlapped with the next chunk's native prep. Every device
    failure still raises at dispatch time, inside this call, so circuit
    and fallback semantics are identical to the synchronous path.

    Returns a PaddedBatch whose ``traces`` are PreparedTrace *views* over
    the batch tensors (rows of the pre-cast f32 arrays), usable by
    assemble_segments unchanged.
    """
    if isinstance(traces_points, TraceBatch):
        B = len(traces_points)
        pt_off = traces_points.offsets
        counts = np.diff(pt_off)
        lat, lon, times = (traces_points.lat, traces_points.lon,
                           traces_points.time)
    else:
        B = len(traces_points)
        counts = [len(pts) for pts in traces_points]
        pt_off = np.zeros(B + 1, dtype=np.int64)
        np.cumsum(counts, out=pt_off[1:])
        n_pts = int(pt_off[-1])
        lat = np.fromiter((p["lat"] for pts in traces_points for p in pts),
                          np.float64, n_pts)
        lon = np.fromiter((p["lon"] for pts in traces_points for p in pts),
                          np.float64, n_pts)
        times = np.fromiter((p["time"] for pts in traces_points for p in pts),
                            np.float64, n_pts)

    use_device = route_kernel is not None and \
        (route_circuit is None or route_circuit.allow())
    if route_kernel is not None and not use_device:
        from ..utils import metrics
        metrics.count("route.device.circuit_skipped_chunks")

    def native_prep(skip_routes: bool) -> dict:
        return runtime.prepare_batch(
            pt_off, lat, lon, times, T, params.max_candidates,
            search_radius=params.search_radius,
            interpolation_distance=params.interpolation_distance,
            breakage_distance=params.breakage_distance,
            max_route_distance_factor=params.max_route_distance_factor,
            backward_tolerance_m=params.backward_tolerance_m,
            max_route_time_factor=params.max_route_time_factor,
            min_time_bound_s=params.min_time_bound_s,
            turn_penalty_factor=params.turn_penalty_factor,
            prune_margin_m=_route_prune_margin(params),
            skip_routes=skip_routes,
            n_threads=n_threads, n_rows=pad_rows)

    out = native_prep(skip_routes=use_device)
    pending = None
    if use_device:
        from ..obs import trace as obs_trace
        from ..utils import metrics
        try:
            with obs_trace.span("prep.routes_device"):
                pending = route_kernel.fill_prep(out, params,
                                                 defer=defer_routes)
        except Exception:
            if route_circuit is not None:
                route_circuit.record_failure()
            metrics.count("route.device.errors")
            metrics.count("route.device.fallback_chunks")
            import logging
            logging.getLogger("reporter_tpu.matcher").warning(
                "device route kernel failed; re-prepping chunk with host "
                "routes", exc_info=True)
            out = native_prep(skip_routes=False)
        else:
            if route_circuit is not None:
                route_circuit.record_success()

    def build_views() -> List[PreparedTrace]:
        if pending is not None:
            pending.write_back(out)
        edge_ids, kept, num_kept = out["edge_ids"], out["kept_idx"], \
            out["num_kept"]
        views = []
        for b in range(B):
            nk = int(num_kept[b])
            views.append(PreparedTrace(
                num_raw=int(counts[b]), num_kept=nk, kept_idx=kept[b, :nk],
                times=times[pt_off[b]:pt_off[b + 1]],
                edge_ids=edge_ids[b], dist_m=out["dist_m"][b],
                offset_m=out["offset_m"][b],
                # the batch tensors carry T time rows (dead trailing
                # step, for seq sharding); the per-trace view keeps the
                # documented (T-1, ...) contract — a contiguous slice,
                # no copy
                route_m=out["route_m"][b, :max(T - 1, 0)],
                gc_m=out["gc_m"][b, :max(T - 1, 0)], case=out["case"][b],
                trailing_jitter_dwell_s=float(out["dwell"][b]),
                has_cands=out["has_cands"][pt_off[b]:pt_off[b + 1]]))
        return views

    # wire dtype: one vectorised decision + cast for the whole batch
    # (sentinels overflow f16 to +inf, which device scoring treats
    # identically — matcher/hmm.py). The cast runs in native code
    # (F16C); numpy's f16 astype was the top host cost after batching.
    dist, route, gc = out["dist_m"], out["route_m"], out["gc_m"]
    finalize = None
    if pending is not None:
        # device-resident: route_m is installed by finalize (the
        # deferred handle may still be a dispatch future on a warm
        # cache); the wire dtype is decided at decode time from the
        # SAME total max the sync path folds (device route bytes are
        # host-identical, so the decision — and therefore the f16
        # quantisation — matches exactly)
        route = None

        def finalize(batch, _p=pending):
            import jax.numpy as jnp

            from ..utils import metrics
            try:
                route_dev, _mx = _p.resolve()
            except Exception:
                # a warm-cache async dispatch died off-thread (device
                # lost mid-flight); the decode lane surfaces it — the
                # chunk has no route bytes to degrade onto anyway
                metrics.count("route.device.finalize_errors")
                raise
            batch.route_m = _device_route_full(route_dev)
            _p.write_back(out)
            if _wire_f16() and float(out["max_finite"][0]) <= WIRE_MAX_M:
                batch.dist_m = runtime.to_f16(out["dist_m"])
                batch.gc_m = runtime.to_f16(out["gc_m"])
                batch.route_m = batch.route_m.astype(jnp.float16)
    elif _wire_f16() and float(out["max_finite"][0]) <= WIRE_MAX_M:
        dist = runtime.to_f16(dist)
        route = runtime.to_f16(route)
        gc = runtime.to_f16(gc)
    return PaddedBatch(traces=_LazyTraceViews(B, build_views), dist_m=dist,
                       valid=out["edge_ids"] != PAD_EDGE, route_m=route,
                       gc_m=gc, case=out["case"], prep=out,
                       pt_off=pt_off, times_flat=times, finalize=finalize)


def _device_route_full(route_dev):
    """Pad a deferred (rows, T-1, K, K) device route tensor out to the
    native wire layout (rows, T, K, K): the dead trailing time step
    carries the UNREACHABLE sentinel — the same bytes the native tail
    fill writes — so every decode shape is identical to the
    host-materialised path. Runs as an async device op; nothing here
    blocks."""
    import jax.numpy as jnp
    return jnp.pad(route_dev, ((0, 0), (0, 1), (0, 0), (0, 0)),
                   constant_values=np.float32(UNREACHABLE))


def _route_prune_margin(params: MatchParams) -> float:
    """Candidate pruning margin in meters (0 = pruning off), from
    REPORTER_TPU_ROUTE_PRUNE_SIGMA x the params' effective sigma. A
    malformed or negative value logs and disables pruning — a typo must
    degrade to the exact (unpruned) semantics, never to surprise drops."""
    spec = _os.environ.get(ENV_PRUNE, "").strip()
    if not spec:
        return 0.0
    try:
        mult = float(spec)
        if mult < 0:
            raise ValueError("must be >= 0")
    except ValueError as e:
        import logging
        logging.getLogger("reporter_tpu.matcher").warning(
            "%s=%r not understood (%s); candidate pruning stays off",
            ENV_PRUNE, spec, e)
        return 0.0
    return mult * float(params.effective_sigma)


def _prune_candidates(cands: CandidateSet, margin: float) -> CandidateSet:
    """Numpy mirror of the native prune block: per point, drop the
    distance-sorted suffix beyond ``dist[0] + margin``. The best
    candidate always survives; pad slots stay pad."""
    if margin <= 0 or cands.edge_ids.size == 0:
        return cands
    live = cands.edge_ids != PAD_EDGE
    cut = (cands.dist_m > cands.dist_m[:, :1] + np.float32(margin)) & live
    if not cut.any():
        return cands
    return CandidateSet(
        edge_ids=np.where(cut, PAD_EDGE, cands.edge_ids),
        dist_m=np.where(cut, PAD_DIST, cands.dist_m),
        offset_m=np.where(cut, np.float32(0.0), cands.offset_m),
        proj_x=cands.proj_x, proj_y=cands.proj_y)


def _wire_f16() -> bool:
    import logging
    import os
    val = os.environ.get("REPORTER_TPU_WIRE", "f16").strip().lower()
    if val not in ("f16", "f32"):
        logging.getLogger("reporter_tpu.matcher").warning(
            "REPORTER_TPU_WIRE=%r not recognised (use f16|f32); keeping f16",
            val)
        return True
    return val != "f32"


def _f16_safe(p: PreparedTrace) -> bool:
    """True when every finite distance in the trace fits the f16 wire
    undistorted (sentinel values >= UNREACHABLE_THRESHOLD travel as +inf;
    the native batched path decides from the C++-computed max_finite
    scalar instead of re-scanning)."""
    if p.gc_m.size and float(np.amax(p.gc_m)) > WIRE_MAX_M:
        return False
    for arr in (p.route_m, p.dist_m):
        if arr.size and float(np.amax(
                arr, initial=0.0,
                where=arr < UNREACHABLE_THRESHOLD)) > WIRE_MAX_M:
            return False
    return True


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def padded_batch_rows(B: int, pad: "int | None", pow2: bool = True) -> int:
    """Batch rows after mesh-multiple + pow2 padding — the ONE padding
    policy shared by pack_batches and the native dispatch (pow2 bounds
    the compiled-shape count per bucket; it never breaks mesh
    divisibility)."""
    rows = B
    if pad:
        rows = ((rows + pad - 1) // pad) * pad
    if pow2:
        p2 = _next_pow2(rows)
        if not pad or p2 % pad == 0:
            rows = p2
    return rows


def pack_batches(prepared: Sequence[PreparedTrace],
                 pad_batch_to: int | None = None,
                 max_batch: int | None = None,
                 pad_pow2: bool = False) -> List[PaddedBatch]:
    """Group prepared traces by bucket length and stack into batches.

    ``pad_batch_to`` optionally rounds the batch dimension up to a multiple
    (useful to keep the compiled-shape count low in a long-running service);
    filler rows are all-SKIP traces that decode to nothing. ``max_batch``
    splits a group into chunks of at most that many traces so host->device
    transfer, decode, and host post-processing of successive chunks can
    overlap (the dispatch pipeline in SegmentMatcher.match_many).
    ``pad_pow2`` additionally rounds the batch dimension up to a power of
    two (after the multiple), bounding the compiled-shape count per bucket
    to log2(max_batch) instead of max_batch — a micro-batching service
    sees every B from 1 to its flush cap over a long run, and each
    distinct B is otherwise a fresh XLA compile stall.

    By default the float tensors are built in the f16 wire format — the
    cast happens inside the copy the pack already performs, halving
    host->device bytes; the unreachable/pad sentinels overflow to +inf,
    which the device scoring treats identically (matcher/hmm.py). A batch
    containing any trace with finite distances beyond f16 range (extreme
    breakage_distance overrides) falls back to f32, as does setting
    REPORTER_TPU_WIRE=f32.
    """
    by_T: dict[int, List[PreparedTrace]] = {}
    for p in prepared:
        by_T.setdefault(p.T, []).append(p)

    # pad and dtype decisions are per T-bucket (one compiled (shape, dtype)
    # per bucket): only buckets actually split by max_batch pad their tail
    # up to the chunk size; small buckets keep their exact B (or the
    # caller's rounding); one out-of-range trace anywhere in a bucket puts
    # the whole bucket on the f32 wire rather than mixing dtypes mid-request
    f16 = _wire_f16()
    chunked: List[tuple] = []  # (T, group, pad, dtype)
    for T, group in sorted(by_T.items()):
        dtype = np.float16 if f16 and all(map(_f16_safe, group)) \
            else np.float32
        if max_batch and len(group) > max_batch:
            chunked.extend((T, group[i:i + max_batch], max_batch, dtype)
                           for i in range(0, len(group), max_batch))
        else:
            chunked.append((T, group, pad_batch_to, dtype))

    batches = []
    for T, group, pad, dtype in chunked:
        B = padded_batch_rows(len(group), pad, pow2=pad_pow2)
        K = group[0].edge_ids.shape[1]
        with np.errstate(over="ignore"):  # sentinels overflow f16 to +inf
            dist = np.full((B, T, K), PAD_DIST, dtype=dtype)
            valid = np.zeros((B, T, K), dtype=bool)
            route = np.full((B, max(T - 1, 0), K, K), UNREACHABLE,
                            dtype=dtype)
            gc = np.zeros((B, max(T - 1, 0)), dtype=dtype)
            case = np.full((B, T), SKIP, dtype=np.int32)
            for b, p in enumerate(group):
                dist[b] = p.dist_m
                valid[b] = p.edge_ids != PAD_EDGE
                route[b] = p.route_m
                gc[b] = p.gc_m
                case[b] = p.case
        batches.append(PaddedBatch(traces=group, dist_m=dist, valid=valid,
                                   route_m=route, gc_m=gc, case=case))
    return batches
