"""Carried per-trace decode state: the incremental Viterbi matcher.

The streaming batcher trims only the consumed prefix of a session's
window (``streaming/batcher.py``), so windows overlap and every
mid-stream report used to re-decode its WHOLE window — O(T·K^2) per
report, forever, per long-lived uuid. This module carries the decode
forward instead: per uuid it keeps the last-step log-scores (K,), a
bounded backpointer ring of the **uncommitted** tail, and the compact
per-step scalars segment assembly actually reads for the committed
prefix; an appended point then costs a candidate row, one route row,
and one step of a batched device advance (``ops/incremental.py``) —
flat in T. A report's appended points are prepped together (one
candidate lookup, one route call) and advanced together: every trace
of a flush, up to ``ROUNDS_MAX`` points each, in one dispatch.

Byte-exact parity with the windowed batch path is the design
constraint, not an aspiration:

- scoring reuses ``hmm.emission_scores``/``transition_scores`` through
  the incremental kernel, and the only reductions involved (max /
  argmax) are exact in f32 — the carried scores are bit-identical to
  the batch scan's running scores at the same step;
- the f16 wire policy mirrors ``batchpad.pack_batches`` per trace: a
  window that would ship f16 quantises every appended step through the
  same f16 round-trip; a window that goes out of f16 range falls back
  to the batch path (the pack would flip the whole window to f32);
- **fixed-lag commit** finalises a ring step only when every current
  state's backtrace converges to the same ancestor there — the
  committed choice provably equals what the final full backtrace would
  pick, whatever is appended later. A window whose ambiguity outlives
  the lag bound falls back to the batch path rather than guess;
- host prep replicates ``batchpad`` semantics step-by-step (kept-point
  selection against the last kept anchor, per-point candidate pruning,
  f32 great-circle casts, breakage RESTARTs, trailing-jitter dwell),
  and assembly runs the same ``assemble_segments`` over a synthesised
  ``PreparedTrace``.

Anything the incremental path cannot reproduce byte-for-byte — bucket
truncation, wire-dtype flips, non-convergent lag windows, state-table
eviction — is a *fallback to the batch path for that trace*, never an
approximation. The windowed decode stays the parity oracle: the shadow
sampler (``REPORTER_TPU_SHADOW_SAMPLE``, PR 8) re-decodes sampled
incremental traces through the full window and compares match bytes.

Knobs: ``REPORTER_TPU_INCREMENTAL`` (kill switch, on by default where
wired), ``REPORTER_TPU_INCREMENTAL_LAG`` (max uncommitted ring steps),
``REPORTER_TPU_INCREMENTAL_MB`` (carried-state byte budget; LRU
eviction beyond it). The table is pressure-ladder-sheddable like the
PR 14 shadow state: the ``shed_trace`` rung suspends the incremental
path and releases its state bytes.
"""
from __future__ import annotations

import json
import logging
import os
import struct
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..core.geo import equirectangular_m
from ..graph.route import UNREACHABLE, candidate_route_matrices
from ..graph.spatial import PAD_DIST, PAD_EDGE, CandidateSet
from ..utils import faults, metrics
from .assemble import assemble_segments
from .batchpad import (LENGTH_BUCKETS, PreparedTrace, _prune_candidates,
                       _route_prune_margin, _wire_f16)
from .hmm import NORMAL, RESTART, UNREACHABLE_THRESHOLD, WIRE_MAX_M

logger = logging.getLogger("reporter_tpu.matcher.incremental")

ENV_INCREMENTAL = "REPORTER_TPU_INCREMENTAL"
ENV_LAG = "REPORTER_TPU_INCREMENTAL_LAG"
ENV_BUDGET_MB = "REPORTER_TPU_INCREMENTAL_MB"

DEFAULT_LAG = 32
DEFAULT_BUDGET_MB = 64.0

#: most rounds (appended points a trace) one device dispatch advances;
#: a dispatch's rounds pad to the next power of four (1, 4, 16, 64) and
#: its traces to a power of two of at least ``MIN_ROWS``, so the programs
#: :meth:`IncrementalTable.warm` builds stay few
ROUNDS_MAX = 64
MIN_ROWS = 8


def incremental_enabled() -> bool:
    """The ``REPORTER_TPU_INCREMENTAL`` kill switch (same grammar as the
    REPORTER_TPU_NATIVE matcher.circuit switch: off|0|false disables)."""
    return os.environ.get(ENV_INCREMENTAL, "").strip().lower() \
        not in ("0", "off", "false")


def lag_bound() -> int:
    """Max uncommitted ring steps per trace before fixed-lag commit must
    land (non-convergence past it falls back to the batch path)."""
    try:
        v = int(os.environ.get(ENV_LAG, "").strip() or DEFAULT_LAG)
        return max(2, v)
    except ValueError:
        return DEFAULT_LAG


def budget_bytes() -> int:
    """Carried-state table byte budget (LRU eviction beyond it)."""
    try:
        v = float(os.environ.get(ENV_BUDGET_MB, "").strip()
                  or DEFAULT_BUDGET_MB)
    except ValueError:
        v = DEFAULT_BUDGET_MB
    return int(max(0.0, v) * 1024 * 1024)


# pressure-ladder rung flag (service/admission.py shed_trace): one global
# load on the hot path, set only on ladder transitions
_pressure_shed = False


def set_pressure_shed(on: bool) -> None:
    global _pressure_shed
    _pressure_shed = bool(on)


def pressure_shed() -> bool:
    return _pressure_shed


class _Fallback(Exception):
    """This trace must be served by the batch path (reason in args[0]).
    Not an error: raised whenever incremental cannot reproduce the batch
    bytes (truncation, wire flip, non-convergent lag window)."""


class _Ring:
    """One uncommitted kept step: full candidate row (assembly needs the
    chosen one, unknown until backtrace), backpointers, and the raw f32
    route row from the previous kept step (assembly reads transition
    scalars; pre-wire values, exactly what ``prepare`` would store)."""

    __slots__ = ("kept_idx", "case", "edge_ids", "offset_m", "bp",
                 "prev_best", "route_in")

    def __init__(self, kept_idx, case, edge_ids, offset_m, bp, prev_best,
                 route_in):
        self.kept_idx = int(kept_idx)
        self.case = int(case)
        self.edge_ids = edge_ids      # (K,) i32
        self.offset_m = offset_m      # (K,) f32
        self.bp = bp                  # (K,) i32 | None (window-first step)
        self.prev_best = int(prev_best)
        self.route_in = route_in      # (K, K) f32 | None (window-first)

    @staticmethod
    def nbytes(K: int) -> int:
        return 4 * K * K + 3 * 4 * K + 64


class _Steps:
    """Host-prepped inputs for a trace's appended kept points, one row
    each, queued for the batched device advance."""

    __slots__ = ("first", "kept_idx", "case", "dist_w", "valid", "route_w",
                 "gc_w", "edge_ids", "offset_m", "route_raw")

    def __init__(self, first, kept_idx, case, dist_w, valid, route_w, gc_w,
                 edge_ids, offset_m, route_raw):
        self.first = first            # row 0 is the window's first point
        self.kept_idx = kept_idx      # (S,) raw indices
        self.case = case              # (S,) i32
        self.dist_w = dist_w          # (S,K) f32, wire round-tripped
        self.valid = valid            # (S,K) bool
        self.route_w = route_w        # (S,K,K) f32, wire round-tripped
        self.gc_w = gc_w              # (S,) f32, wire round-tripped
        self.edge_ids = edge_ids      # (S,K) i32 (pruned)
        self.offset_m = offset_m      # (S,K) f32 (pruned)
        self.route_raw = route_raw    # (S,K,K) f32 pre-wire (row 0 unused
        #                               at the window's first point)

    def __len__(self) -> int:
        return len(self.case)


class CarriedState:
    """Everything one uuid's decode carries between appended points."""

    __slots__ = ("params_key", "f16", "K", "map_version",
                 "t0", "last_time", "n_raw",
                 "has_cands", "last_kept_raw", "last_lat", "last_lon",
                 "tail_ok", "prev_cand", "scores",
                 "c_kept", "c_case", "c_col", "c_edge", "c_off", "c_route",
                 "ring")

    def __init__(self, params_key, f16: bool, K: int,
                 map_version: Optional[str] = None):
        self.params_key = params_key
        self.f16 = bool(f16)
        self.K = int(K)
        # the graph build this state's edge ids/backpointers belong to
        # (graph/version.py); part of the cache identity — a hot swap
        # must never serve segment ids decoded against a dead graph
        self.map_version = map_version
        self.t0 = 0.0                 # first raw time of the window
        self.last_time = 0.0          # last processed raw time
        self.n_raw = 0                # raw points processed
        self.has_cands: List[bool] = []
        self.last_kept_raw = -1       # raw index of the last kept point
        self.last_lat = 0.0
        self.last_lon = 0.0
        self.tail_ok = True           # raw tail since last kept is jitter
        self.prev_cand = None         # pruned (K,) candidate row arrays
        self.scores: Optional[np.ndarray] = None  # (K,) f32 carried
        # committed prefix: the scalars assembly reads, one per step
        self.c_kept: List[int] = []   # raw index
        self.c_case: List[int] = []
        self.c_col: List[int] = []    # chosen candidate column
        self.c_edge: List[int] = []
        self.c_off: List[float] = []
        self.c_route: List[float] = []  # route to NEXT committed step
        self.ring: List[_Ring] = []

    @property
    def n_kept(self) -> int:
        return len(self.c_kept) + len(self.ring)

    def nbytes(self) -> int:
        K = self.K
        return (256 + len(self.has_cands)
                + 40 * len(self.c_kept)
                + len(self.ring) * _Ring.nbytes(K)
                + 5 * 4 * K)

    # -- snapshot serde (state snapshot v3) --------------------------------
    _HEAD = struct.Struct("<BBHddiiq??dd")

    def to_bytes(self) -> bytes:
        """Self-contained blob for the v3 state snapshot. Scalars are
        struct-packed, arrays raw ``tobytes`` with shapes implied by K
        and the packed counts."""
        K = self.K
        key = np.asarray(self.params_key, dtype=np.float64)
        out = [self._HEAD.pack(2, int(self.f16), K, self.t0,
                               self.last_time, self.n_raw,
                               self.last_kept_raw, len(self.c_kept),
                               self.tail_ok, self.prev_cand is not None,
                               self.last_lat, self.last_lon),
               struct.pack("<HH", len(key), len(self.ring)),
               key.tobytes(),
               np.packbits(np.asarray(self.has_cands, dtype=bool)
                           ).tobytes()]
        if self.prev_cand is not None:
            e, d, o, px, py = self.prev_cand
            out += [e.tobytes(), d.tobytes(), o.tobytes(),
                    px.tobytes(), py.tobytes()]
        sc = self.scores if self.scores is not None \
            else np.zeros(0, dtype=np.float32)
        out.append(struct.pack("<H", len(sc)))
        out.append(sc.tobytes())
        out.append(np.asarray(self.c_kept, dtype=np.int32).tobytes())
        out.append(np.asarray(self.c_case, dtype=np.int8).tobytes())
        out.append(np.asarray(self.c_col, dtype=np.int16).tobytes())
        out.append(np.asarray(self.c_edge, dtype=np.int32).tobytes())
        out.append(np.asarray(self.c_off, dtype=np.float32).tobytes())
        out.append(np.asarray(self.c_route, dtype=np.float32).tobytes())
        for r in self.ring:
            first = r.bp is None
            out.append(struct.pack("<iiB?", r.kept_idx, r.case,
                                   r.prev_best, first))
            out += [r.edge_ids.tobytes(), r.offset_m.tobytes()]
            if not first:
                out += [r.bp.tobytes(), r.route_in.tobytes()]
        # v2 trailer: the graph version the state was decoded against
        mv = (self.map_version or "").encode()
        out.append(struct.pack("<H", len(mv)))
        out.append(mv)
        return b"".join(out)

    @classmethod
    def from_bytes(cls, blob: bytes) -> "CarriedState":
        off = 0

        def take(n):
            nonlocal off
            if off + n > len(blob):
                raise ValueError("truncated carried-state blob")
            b = blob[off:off + n]
            off += n
            return b

        (ver, f16, K, t0, last_time, n_raw, last_kept, n_c, tail_ok,
         has_prev, last_lat, last_lon) = cls._HEAD.unpack(
            take(cls._HEAD.size))
        if ver not in (1, 2):
            raise ValueError(f"carried-state version {ver} unsupported")
        n_key, n_ring = struct.unpack("<HH", take(4))
        key = tuple(np.frombuffer(take(8 * n_key), dtype=np.float64)
                    .tolist())
        st = cls(key, bool(f16), K)
        st.t0, st.last_time, st.n_raw = t0, last_time, n_raw
        st.last_kept_raw = last_kept
        st.tail_ok = bool(tail_ok)
        st.last_lat, st.last_lon = last_lat, last_lon
        bits = np.frombuffer(take((n_raw + 7) // 8), dtype=np.uint8)
        st.has_cands = np.unpackbits(bits, count=n_raw).astype(bool) \
            .tolist()
        if has_prev:
            e = np.frombuffer(take(4 * K), dtype=np.int32)
            d = np.frombuffer(take(4 * K), dtype=np.float32)
            o = np.frombuffer(take(4 * K), dtype=np.float32)
            px = np.frombuffer(take(4 * K), dtype=np.float32)
            py = np.frombuffer(take(4 * K), dtype=np.float32)
            st.prev_cand = (e, d, o, px, py)
        (n_sc,) = struct.unpack("<H", take(2))
        sc = np.frombuffer(take(4 * n_sc), dtype=np.float32)
        st.scores = sc.copy() if n_sc else None
        st.c_kept = np.frombuffer(take(4 * n_c), np.int32).tolist()
        st.c_case = np.frombuffer(take(1 * n_c), np.int8).tolist()
        st.c_col = np.frombuffer(take(2 * n_c), np.int16).tolist()
        st.c_edge = np.frombuffer(take(4 * n_c), np.int32).tolist()
        st.c_off = np.frombuffer(take(4 * n_c), np.float32).tolist()
        st.c_route = np.frombuffer(take(4 * n_c), np.float32).tolist()
        for _ in range(n_ring):
            kept_idx, case, prev_best, first = struct.unpack(
                "<iiB?", take(10))
            edge = np.frombuffer(take(4 * K), dtype=np.int32)
            offm = np.frombuffer(take(4 * K), dtype=np.float32)
            bp = route_in = None
            if not first:
                bp = np.frombuffer(take(4 * K), dtype=np.int32)
                route_in = np.frombuffer(take(4 * K * K), dtype=np.float32
                                         ).reshape(K, K)
            st.ring.append(_Ring(kept_idx, case, edge, offm, bp,
                                 prev_best, route_in))
        if ver >= 2:
            (n_mv,) = struct.unpack("<H", take(2))
            mv = take(n_mv).decode()
            st.map_version = mv or None
        # ver 1 blobs predate graph versioning: map_version stays None,
        # which a versioned table treats as a mismatch — the trace
        # re-decodes from its window rather than trusting edge ids of
        # unknown provenance
        return st


def _wire_roundtrip(arr: np.ndarray) -> np.ndarray:
    """The f16 wire quantisation pack_batches applies, as a value map:
    f32 -> f16 -> f32 (sentinels overflow to +inf, upcast intact —
    exactly what the device decode sees after the wire)."""
    with np.errstate(over="ignore"):
        return arr.astype(np.float16).astype(np.float32)


def _round_inputs(rounds: int, rows: int, K: int) -> tuple:
    """One dispatch's padded kernel inputs (dist, valid, route, gc, case,
    live, prev) for ``rounds`` rounds of ``rows`` traces, every slot a
    RESTART onto no candidate, and not live, until a trace's step fills
    it."""
    return (np.full((rounds, rows, K), PAD_DIST, dtype=np.float32),
            np.zeros((rounds, rows, K), dtype=bool),
            np.full((rounds, rows, K, K), UNREACHABLE, dtype=np.float32),
            np.zeros((rounds, rows), dtype=np.float32),
            np.full((rounds, rows), RESTART, dtype=np.int32),
            np.zeros((rounds, rows), dtype=bool),
            np.zeros((rows, K), dtype=np.float32))


def _round_bucket(n: int) -> int:
    """The rounds a dispatch of ``n`` (at most ``ROUNDS_MAX``) pads to."""
    b = 1
    while b < n:
        b *= 4
    return b


def _row_bucket(n: int) -> int:
    """The rows a dispatch of ``n`` traces pads to."""
    return max(MIN_ROWS, 1 << max(n - 1, 0).bit_length())


def _select_appended(lat, lon, idx, anchor, interpolation_distance):
    """Raw indices of the appended candidate-bearing points ``idx`` that
    enter the HMM: ``batchpad._select_kept``'s moving anchor continued
    from ``anchor``, the (lat, lon) of the window's last kept point, or
    from the first of ``idx`` where the window has none. A point within
    ``interpolation_distance`` of the anchor is dropped, and a point
    beyond it becomes the anchor. The distances from every possible
    anchor to every point are one vector call; the walk reads them."""
    if idx.size == 0:
        return idx
    kept = []
    if anchor is None:
        kept.append(int(idx[0]))
        anchor = (lat[idx[0]], lon[idx[0]])
        idx = idx[1:]
    a_lat = np.concatenate([[anchor[0]], lat[idx]])
    a_lon = np.concatenate([[anchor[1]], lon[idx]])
    reach = np.atleast_2d(equirectangular_m(
        a_lat[:, None], a_lon[:, None], lat[idx][None, :],
        lon[idx][None, :]))
    row = 0                   # the anchor's row: 0, or 1 + its column
    for j, i in enumerate(idx.tolist()):
        if reach[row, j] >= interpolation_distance:
            kept.append(i)
            row = j + 1
    return np.asarray(kept, dtype=np.int64)


class IncrementalTable:
    """uuid -> :class:`CarriedState`, byte-budgeted with LRU eviction.

    Owned by a :class:`SegmentMatcher` (``matcher.incremental_table``);
    all device work goes through ``ops.incremental_step_batch`` so N
    traces advance per dispatch. Mutations run under one lock — the
    streaming worker advances from its flush thread while /health and
    the heartbeat read the gauge from theirs.
    """

    def __init__(self, matcher):
        self.matcher = matcher
        # cache identity includes the graph build (graph/version.py):
        # a city hot swap rebuilds the matcher around a new net, and
        # every carried state minted against the old one must reset
        # instead of serving segment ids from a dead graph
        try:
            from ..graph.version import map_version
            self.map_version: Optional[str] = map_version(matcher.net)
        except Exception:
            self.map_version = None
        self._states: Dict[str, CarriedState] = {}
        self._order: List[str] = []   # LRU, oldest first
        self._lock = threading.Lock()
        self._bytes = 0
        self.evictions = 0
        self.fallbacks = 0
        self.resets = 0

    # -- gauges ------------------------------------------------------------
    def gauge(self) -> dict:
        with self._lock:
            return {"traces": len(self._states),
                    "map_version": self.map_version,
                    "state_bytes": self._bytes,
                    "budget_bytes": budget_bytes(),
                    "lag": lag_bound(),
                    "evictions": self.evictions,
                    "fallbacks": self.fallbacks,
                    "resets": self.resets}

    def _recount(self) -> None:
        self._bytes = sum(s.nbytes() for s in self._states.values())

    def _touch(self, uuid: str) -> None:
        try:
            self._order.remove(uuid)
        except ValueError:
            pass
        self._order.append(uuid)

    def evict(self, uuid: str, reason: str = "evicted") -> None:
        with self._lock:
            if self._states.pop(uuid, None) is not None:
                try:
                    self._order.remove(uuid)
                except ValueError:
                    pass
                self.evictions += 1
                metrics.count("match.incremental.evictions")
                self._recount()
                logger.debug("carried state for %s %s", uuid, reason)

    def clear(self) -> None:
        """Drop every carried state (pressure shed / kill switch)."""
        with self._lock:
            n = len(self._states)
            self._states.clear()
            self._order.clear()
            self._bytes = 0
            if n:
                self.evictions += n
                metrics.count("match.incremental.evictions", n)

    def _enforce_budget(self, keep: Optional[str] = None) -> None:
        """LRU-evict until under budget (called with the lock held)."""
        budget = budget_bytes()
        while self._bytes > budget and self._order:
            victim = None
            for u in self._order:
                if u != keep:
                    victim = u
                    break
            if victim is None:
                victim = self._order[0]  # even the active trace goes
            self._states.pop(victim, None)
            self._order.remove(victim)
            self.evictions += 1
            metrics.count("match.incremental.evictions")
            self._recount()

    # -- snapshot serde ----------------------------------------------------
    def to_blobs(self) -> List[tuple]:
        """[(uuid, blob)] for the v3 state snapshot."""
        with self._lock:
            return [(u, s.to_bytes()) for u, s in self._states.items()]

    def restore_blobs(self, blobs) -> int:
        """Load [(uuid, blob)] from a v3 snapshot; returns count loaded.
        A blob that fails to parse is skipped (that trace re-decodes
        from its window on the next report — correctness is unaffected,
        the snapshot only buys work avoidance)."""
        n = 0
        with self._lock:
            for uuid, blob in blobs:
                try:
                    self._states[uuid] = CarriedState.from_bytes(blob)
                    self._touch(uuid)
                    n += 1
                except Exception as e:
                    logger.warning("carried state for %s failed to "
                                   "restore (%s); it will re-decode",
                                   uuid, e)
            self._recount()
        return n

    # -- the advance + match path ------------------------------------------
    def match_many(self, tb, per_trace_params, results) -> int:
        """Advance carried state for every trace of ``tb`` with a uuid
        and fill ``results[i]`` with a match dict; slots left None fall
        back to the batch path. Returns the number of per-trace
        failures (real errors, not parity fallbacks)."""
        lag = lag_bound()
        jobs = []   # [i, uuid, state, steps, params, alive]
        failures = 0
        with self._lock:
            try:
                # decode cost — prep of the appended points, the kernel
                # rounds, and the fixed-lag commits — timed apart from
                # the serve assembly below: the O(K)-per-point claim
                # (and the bench gate on it) is about THIS span, while
                # assembly is the O(window) report-emission cost the
                # batch path pays identically
                t_dec = time.perf_counter()
                with metrics.timer("match.incremental.prep"):
                    for i in range(len(tb)):
                        job = self._prep_job(tb, i, per_trace_params[i])
                        if job is not None:
                            jobs.append(job)
                with metrics.timer("match.incremental.rounds"):
                    failures += self._run_rounds(jobs, lag)
                metrics.observe("match.incremental.decode",
                                time.perf_counter() - t_dec)

                for i, uuid, state, steps, params, alive in jobs:
                    if not alive:
                        continue
                    _lat, _lon, times = tb.trace_columns(i)
                    results[i] = self._build_match(state, times, params)
                    self._touch(uuid)
                    metrics.count("match.incremental.matches")
            except Exception:
                # a mid-advance error leaves SOME state half-stepped
                # (n_raw past the scores) — drop every state this call
                # touched so nothing stale survives to the next report
                failures += 1
                metrics.count("match.incremental.errors")
                for job in jobs:
                    self._drop(job[1])
                self._recount()
                raise
            delta = -self._bytes
            self._recount()
            delta += self._bytes
            if delta:
                metrics.count("match.incremental.state_bytes", delta)
            keep = jobs[-1][1] if jobs else None
            self._enforce_budget(keep=keep)
        # shadow parity sampling runs outside the lock (it re-preps and
        # re-decodes the full window)
        for i, uuid, state, steps, params, alive in jobs:
            if alive and results[i] is not None:
                lat, lon, times = tb.trace_columns(i)
                _maybe_shadow(self.matcher, lat, lon, times, params,
                              results[i])
        return failures

    def _prep_job(self, tb, i: int, params) -> Optional[list]:
        """Host prep of trace ``i``'s appended points: its job
        ``[i, uuid, state, steps, params, alive]``, or None where it has
        no uuid or no points, or falls back to the batch path."""
        uuid = tb.uuid(i)
        if not uuid:
            return None
        lat, lon, times = tb.trace_columns(i)
        if len(times) == 0:
            return None
        try:
            state = self._state_for(uuid, params, times)
            steps = self._prep_appended(state, params, lat, lon, times)
        except _Fallback as fb:
            self.fallbacks += 1
            metrics.count("match.incremental.fallbacks")
            logger.debug("trace %s falls back to the batch path (%s)",
                         uuid, fb)
            self._drop(uuid)
            return None
        return [i, uuid, state, steps, params, True]

    def _drop(self, uuid: str) -> None:
        """Lock-held eviction (fallback/error paths)."""
        if self._states.pop(uuid, None) is not None:
            try:
                self._order.remove(uuid)
            except ValueError:
                pass

    def _state_for(self, uuid, params, times) -> CarriedState:
        key = tuple(
            float(getattr(params, f))
            for f in type(self.matcher)._PREP_KEY_FIELDS)
        f16 = _wire_f16()
        n = len(times)
        st = self._states.get(uuid)
        if st is not None:
            ok = (st.params_key == key and st.f16 == f16
                  and st.map_version == self.map_version
                  and 0 < st.n_raw <= n
                  and st.t0 == float(times[0])
                  and st.last_time == float(times[st.n_raw - 1]))
            if not ok:
                # window identity changed: the batcher trimmed at
                # shape_used (or a new session reused the uuid) — the
                # batch oracle frames the new window with RESTART at its
                # first kept point, so the carried chain resets and the
                # short surviving window replays incrementally
                self._drop(uuid)
                self.resets += 1
                metrics.count("match.incremental.resets")
                st = None
        if st is None:
            st = CarriedState(key, f16, int(params.max_candidates),
                              map_version=self.map_version)
            self._states[uuid] = st
            self._touch(uuid)
        return st

    def _prep_appended(self, state: CarriedState, params, lat, lon,
                       times) -> Optional[_Steps]:
        """Host prep for raw points [state.n_raw, len(times)): one
        candidate lookup for them all, kept-point selection, pruning and
        the route rows from each kept point's predecessor — batchpad's
        semantics, continued from the carried selection state (None
        where no point is kept). Mutates selection state as it goes
        (any later failure evicts)."""
        m = self.matcher
        K = state.K
        n = len(times)
        a = state.n_raw
        if a == 0:
            state.t0 = float(times[0])
        if a >= n:
            return None
        lookup = m.runtime if m.runtime is not None else m.grid
        rows = lookup.candidates(lat[a:n], lon[a:n], K, params.search_radius)
        has = (rows.edge_ids != PAD_EDGE).any(axis=1)
        state.has_cands.extend(has.tolist())
        state.n_raw = n
        state.last_time = float(times[n - 1])
        anchor = ((state.last_lat, state.last_lon)
                  if state.last_kept_raw >= 0 else None)
        kept = _select_appended(lat, lon, a + np.flatnonzero(has), anchor,
                                params.interpolation_distance)
        if kept.size == 0:
            # an off-network point in the tail ends its dwell; jitter
            # drops leave it as it was
            state.tail_ok = state.tail_ok and bool(has.all())
            return None
        state.tail_ok = bool(has[int(kept[-1]) - a + 1:].all())
        if state.n_kept + kept.size > LENGTH_BUCKETS[-1]:
            # the batch path truncates at the largest bucket; that
            # semantics is window-global, not per-step
            raise _Fallback("window exceeds the largest bucket")
        sel = kept - a
        pruned = _prune_candidates(
            CandidateSet(edge_ids=rows.edge_ids[sel], dist_m=rows.dist_m[sel],
                         offset_m=rows.offset_m[sel],
                         proj_x=rows.proj_x[sel], proj_y=rows.proj_y[sel]),
            _route_prune_margin(params))
        steps = self._make_steps(state, params, pruned, lat, lon, times,
                                 kept)
        j = int(kept[-1])
        state.last_kept_raw = j
        state.last_lat = float(lat[j])
        state.last_lon = float(lon[j])
        state.prev_cand = tuple(
            np.ascontiguousarray(x[-1]) for x in (
                pruned.edge_ids, pruned.dist_m, pruned.offset_m,
                pruned.proj_x, pruned.proj_y))
        return steps

    def _make_steps(self, state, params, pruned, lat, lon, times,
                    kept) -> _Steps:
        """Route rows + case codes + wire casts of the appended kept
        points ``kept`` (raw indices; ``pruned`` their candidates): the
        route rows in one call over the chain from the window's previous
        kept point, as the batch path's prep makes them."""
        m = self.matcher
        K = state.K
        S = kept.size
        first = state.last_kept_raw < 0
        dist = np.ascontiguousarray(pruned.dist_m)
        valid = pruned.edge_ids != PAD_EDGE
        case = np.full(S, NORMAL, dtype=np.int32)
        gc32 = np.zeros(S, dtype=np.float32)
        # a window's first kept point has no route in: its row stays
        # all unreachable, as the RESTART it is
        route_raw = np.full((S, K, K), UNREACHABLE, dtype=np.float32)
        if first:
            case[0] = RESTART
            lo, chain = 1, pruned
            p_lat, p_lon = lat[kept[:-1]], lon[kept[:-1]]
            p_time = times[kept[:-1]]
        else:
            lo = 0
            pe, pd, po, ppx, ppy = state.prev_cand
            chain = CandidateSet(
                edge_ids=np.concatenate([pe[None], pruned.edge_ids]),
                dist_m=np.concatenate([pd[None], dist]),
                offset_m=np.concatenate([po[None], pruned.offset_m]),
                proj_x=np.concatenate([ppx[None], pruned.proj_x]),
                proj_y=np.concatenate([ppy[None], pruned.proj_y]))
            p_lat = np.concatenate([[state.last_lat], lat[kept[:-1]]])
            p_lon = np.concatenate([[state.last_lon], lon[kept[:-1]]])
            p_time = np.concatenate([[times[state.last_kept_raw]],
                                     times[kept[:-1]]])
        if S > lo:
            gc32[lo:] = np.atleast_1d(equirectangular_m(
                p_lat, p_lon, lat[kept[lo:]], lon[kept[lo:]]))
            case[lo:] = np.where(gc32[lo:] > params.breakage_distance,
                                 RESTART, NORMAL)
            dt = None
            if params.max_route_time_factor > 0:
                dt = times[kept[lo:]] - p_time
            kw = dict(max_route_distance_factor=params
                      .max_route_distance_factor,
                      backward_tolerance_m=params.backward_tolerance_m,
                      dt=dt,
                      max_route_time_factor=params.max_route_time_factor,
                      min_time_bound_s=params.min_time_bound_s,
                      turn_penalty_factor=params.turn_penalty_factor)
            if m.runtime is not None:
                route = m.runtime.route_matrices(chain, gc32[lo:], **kw)
            else:
                route = candidate_route_matrices(
                    m.net, chain, gc32[lo:], cache=m.route_cache, **kw)
            route_raw[lo:] = route
        dist_w, route_w, gc_w = dist, route_raw, gc32
        if state.f16:
            # per-trace mirror of the pack_batches wire decision: a
            # finite value out of f16 range would flip the WHOLE window
            # to the f32 wire in the batch path — history the carried
            # f16 scores can't rewrite, so fall back instead
            fin_d = float(np.amax(dist, initial=0.0,
                                  where=dist < UNREACHABLE_THRESHOLD))
            fin_r = float(np.amax(route_raw, initial=0.0,
                                  where=route_raw < UNREACHABLE_THRESHOLD))
            if max(fin_d, fin_r, float(gc32.max())) > WIRE_MAX_M:
                raise _Fallback("finite distance beyond the f16 wire")
            dist_w = _wire_roundtrip(dist)
            route_w = _wire_roundtrip(route_raw)
            gc_w = _wire_roundtrip(gc32)
        return _Steps(first, kept, case, dist_w, valid, route_w, gc_w,
                      np.ascontiguousarray(pruned.edge_ids),
                      np.ascontiguousarray(pruned.offset_m), route_raw)

    def _run_rounds(self, jobs, lag: int) -> int:
        """Advance every job's queued steps through the batched kernel:
        per group of jobs sharing the device scalars, one dispatch for
        each ``ROUNDS_MAX`` rounds (round r = each trace's r-th step).
        Returns per-round failure count."""
        from ..ops import incremental_step_batch
        failures = 0
        # group rows by the device scalars (one kernel call each); the
        # steady state is a single shared params object
        groups: Dict[tuple, list] = {}
        for job in jobs:
            if job[3] is None:
                continue
            p = job[4]
            gkey = (float(p.effective_sigma), float(p.beta),
                    int(p.max_candidates))
            groups.setdefault(gkey, []).append(job)
        for (sigma, beta, K), grp in groups.items():
            r0 = 0
            while True:
                rows = [job for job in grp if job[5] and len(job[3]) > r0]
                if not rows:
                    break
                self._dispatch(rows, r0, K, sigma, beta,
                               incremental_step_batch, lag)
                r0 += ROUNDS_MAX
        return failures

    def warm(self, max_rows: int) -> int:
        """Build, before a measured stretch of work, the device advance
        at every shape a dispatch of up to ``max_rows`` traces pads to
        (rows: the powers of two from ``MIN_ROWS`` up to the one that
        holds it; rounds: 1, 4, 16 and 64), at the matcher's K, sigma and
        beta. Returns the shapes built."""
        from ..ops import incremental_step_batch
        p = self.matcher.params
        K = int(p.max_candidates)
        built = 0
        rows = MIN_ROWS
        while True:
            rounds = 1
            while rounds <= ROUNDS_MAX:
                out = incremental_step_batch(
                    *_round_inputs(rounds, rows, K),
                    np.float32(p.effective_sigma), np.float32(p.beta))
                np.asarray(out[0])
                built += 1
                rounds *= 4
            if rows >= max_rows:
                return built
            rows *= 2

    def _dispatch(self, grp, r0, K, sigma, beta, kernel, lag) -> None:
        """Rounds [r0, r0 + ROUNDS_MAX) of the jobs in ``grp`` in one
        kernel call, then each trace's ring and fixed-lag commits."""
        spans = [(r0, min(len(job[3]), r0 + ROUNDS_MAX)) for job in grp]
        rounds = _round_bucket(max(hi for _lo, hi in spans) - r0)
        dist, valid, route, gc, case, live, prev = _round_inputs(
            rounds, _row_bucket(len(grp)), K)
        for b, (job, (lo, hi)) in enumerate(zip(grp, spans)):
            s, st, k = job[3], job[2], hi - lo
            dist[:k, b] = s.dist_w[lo:hi]
            valid[:k, b] = s.valid[lo:hi]
            route[:k, b] = s.route_w[lo:hi]
            gc[:k, b] = s.gc_w[lo:hi]
            case[:k, b] = s.case[lo:hi]
            live[:k, b] = True
            if st.scores is not None:
                prev[b] = st.scores
        scores, bp, prev_best = kernel(
            dist, valid, route, gc, case, live, prev,
            np.float32(sigma), np.float32(beta))
        scores = np.asarray(scores)
        bp = np.asarray(bp)
        prev_best = np.asarray(prev_best)
        metrics.count("match.incremental.steps",
                      sum(hi - lo for lo, hi in spans))
        for b, (job, (lo, hi)) in enumerate(zip(grp, spans)):
            s, st = job[3], job[2]
            try:
                for t in range(lo, hi):
                    first = s.first and t == 0
                    st.ring.append(_Ring(
                        s.kept_idx[t], s.case[t], s.edge_ids[t],
                        s.offset_m[t],
                        None if first else bp[t - r0, b].copy(),
                        0 if first else int(prev_best[t - r0, b]),
                        None if first else s.route_raw[t]))
                    while len(st.ring) > lag:
                        self._commit_one(st)
                st.scores = scores[b].copy()
            except _Fallback as fb:
                self.fallbacks += 1
                metrics.count("match.incremental.fallbacks")
                logger.debug("trace %s falls back to the batch path "
                             "(%s)", job[1], fb)
                job[5] = False
                self._drop(job[1])

    def _commit_one(self, st: CarriedState) -> None:
        """Fixed-lag commit of the oldest ring step: finalise its choice
        iff every current state's backtrace converges there. The
        converged ancestor provably equals what the final backtrace
        will pick — whatever gets appended later enters ABOVE these
        steps, so the pointer chase below them never changes."""
        cur = range(st.K)
        for e in reversed(st.ring[1:]):
            if e.case == RESTART:
                cur = (e.prev_best,)
            elif len(cur) == 1:
                # converged: one ancestor from here back
                cur = (int(e.bp[cur[0]]),)
            else:
                cur = tuple({int(e.bp[i]) for i in cur})
        if len(cur) != 1:
            raise _Fallback("lag window did not converge")
        (c,) = cur
        faults.failpoint("match.incremental.commit")
        e0 = st.ring.pop(0)
        if st.c_kept and e0.route_in is not None:
            # the transition INTO this step, at the now-known choice
            # pair, becomes the previous committed step's outgoing
            # route scalar (what assembly reads)
            st.c_route[-1] = float(e0.route_in[st.c_col[-1], c])
        st.c_kept.append(e0.kept_idx)
        st.c_case.append(e0.case)
        st.c_col.append(c)
        st.c_edge.append(int(e0.edge_ids[c]))
        st.c_off.append(float(e0.offset_m[c]))
        st.c_route.append(float(UNREACHABLE))   # until the next commit
        metrics.count("match.incremental.commits")

    def _build_match(self, st: CarriedState, times, params) -> dict:
        """Synthesise a PreparedTrace + decoded path from the carried
        state and run the SAME scalar assembly as the batch fallback
        path — byte-identical match dicts by construction."""
        K = st.K
        nc = len(st.c_kept)
        n = st.n_kept
        # live-tail backtrace (the batch backward pass over the ring)
        ring_path: List[int] = []
        if st.ring:
            cur = int(np.argmax(st.scores))
            ring_path = [cur]
            for e in reversed(st.ring[1:]):
                cur = e.prev_best if e.case == RESTART else int(e.bp[cur])
                ring_path.append(cur)
            ring_path.reverse()
        path = np.zeros(max(n, 1), dtype=np.int32)
        path[nc:n] = ring_path

        edge_ids = np.full((n, K), PAD_EDGE, dtype=np.int32)
        offset = np.zeros((n, K), dtype=np.float32)
        case = np.zeros(n, dtype=np.int32)
        kept_idx = np.zeros(n, dtype=np.int32)
        route_m = np.full((max(n - 1, 0), K, K), UNREACHABLE,
                          dtype=np.float32)
        if nc:
            kept_idx[:nc] = st.c_kept
            case[:nc] = st.c_case
            edge_ids[:nc, 0] = st.c_edge
            offset[:nc, 0] = st.c_off
            # committed->committed transitions live at the (0, 0) cell
            # the all-zero committed path indexes
            route_m[:max(nc - 1, 0), 0, 0] = st.c_route[:nc - 1] \
                if nc > 1 else []
        for t, e in enumerate(st.ring):
            kept_idx[nc + t] = e.kept_idx
            case[nc + t] = e.case
            edge_ids[nc + t] = e.edge_ids
            offset[nc + t] = e.offset_m
            if e.route_in is None:
                continue
            if t == 0 and nc:
                # last committed -> first ring step: the committed side
                # sits in column 0, the ring side keeps its true index
                route_m[nc - 1, 0, :] = e.route_in[st.c_col[-1], :]
            elif t > 0:
                route_m[nc + t - 1] = e.route_in
        dwell = 0.0
        if n and st.last_kept_raw < st.n_raw - 1 and st.tail_ok:
            dwell = float(times[st.n_raw - 1] - times[st.last_kept_raw])
        prepared = PreparedTrace(
            num_raw=st.n_raw, num_kept=n, kept_idx=kept_idx,
            times=np.asarray(times), edge_ids=edge_ids, dist_m=offset * 0,
            offset_m=offset, route_m=route_m,
            gc_m=np.zeros(max(n - 1, 0), dtype=np.float32), case=case,
            trailing_jitter_dwell_s=dwell,
            has_cands=np.asarray(st.has_cands, dtype=bool))
        return assemble_segments(
            self.matcher.net, prepared, path, mode=params.mode,
            queue_threshold_kph=params.queue_speed_threshold_kph,
            interpolation_distance_m=params.interpolation_distance,
            backward_tolerance_m=params.backward_tolerance_m,
            turn_penalty_factor=params.turn_penalty_factor)


# -- shadow parity oracle (the PR 8 sampler, generalised) -------------------

_shadow_lock = threading.Lock()
_shadow_acc = 0.0


def _maybe_shadow(matcher, lat, lon, times, params, match) -> None:
    """Deterministic-accumulator sampling (REPORTER_TPU_SHADOW_SAMPLE,
    shared with the decode shadow): re-decode this trace's FULL window
    through the batch oracle (prepare -> wire cast -> numpy Viterbi ->
    scalar assembly) and compare match bytes. A mismatch is a parity
    bug, counted and logged — the incremental result still serves (the
    sampler observes, the circuit + fallbacks act)."""
    from ..obs import profiler
    frac = profiler.shadow_fraction()
    if frac <= 0.0:
        return
    global _shadow_acc
    with _shadow_lock:
        _shadow_acc += min(frac, 1.0)
        if _shadow_acc < 1.0:
            return
        _shadow_acc -= 1.0
    try:
        oracle = _oracle_match(matcher, lat, lon, times, params)
        a = json.dumps(match, sort_keys=True)
        b = json.dumps(oracle, sort_keys=True)
        metrics.count("match.incremental.shadow_checks")
        if a != b:
            metrics.count("match.incremental.shadow_mismatches")
            logger.warning(
                "incremental/batch parity mismatch on a %d-point window "
                "(incremental %d bytes, oracle %d bytes)",
                len(times), len(a), len(b))
    except Exception as e:   # the sampler must never take down serving
        metrics.count("match.incremental.shadow_errors")
        logger.warning("incremental shadow check failed: %s", e)


def _oracle_match(matcher, lat, lon, times, params) -> dict:
    """The windowed batch path for one trace, end to end on the host:
    prepare -> pack (wire dtype decision included) -> numpy Viterbi
    oracle -> scalar assembly. This is the parity definition the bench
    and tests hold the incremental path to."""
    from .batchpad import pack_batches
    from .cpu_ref import viterbi_decode_numpy
    points = [{"lat": float(lat[j]), "lon": float(lon[j]),
               "time": float(times[j])} for j in range(len(times))]
    prep = matcher.prepare(points, params)
    batch = pack_batches([prep])[0]
    T = batch.dist_m.shape[1]
    path, _score = viterbi_decode_numpy(
        np.asarray(batch.dist_m[0], dtype=np.float32),
        np.asarray(batch.valid[0]),
        np.asarray(batch.route_m[0, :max(T - 1, 0)], dtype=np.float32),
        np.asarray(batch.gc_m[0, :max(T - 1, 0)], dtype=np.float32),
        np.asarray(batch.case[0]),
        np.float32(params.effective_sigma), np.float32(params.beta))
    return assemble_segments(
        matcher.net, prep, path, mode=params.mode,
        queue_threshold_kph=params.queue_speed_threshold_kph,
        interpolation_distance_m=params.interpolation_distance,
        backward_tolerance_m=params.backward_tolerance_m,
        turn_penalty_factor=params.turn_penalty_factor)


__all__ = ["IncrementalTable", "CarriedState", "incremental_enabled",
           "lag_bound", "budget_bytes", "set_pressure_shed",
           "pressure_shed"]
