"""Pure-numpy single-trace Viterbi — the reference-architecture analog.

Two jobs:

1. **Bench baseline.** The reference decodes one trace at a time on one
   CPU thread inside C++ Meili (reference: py/reporter_service.py:240,
   Batch.java:66-68). This module is the closest in-repo analog of that
   one Meili thread: same emission/transition semantics as the device
   kernels, no XLA, no batching — what bench.py's ``vs_baseline`` ratio
   is measured against (BASELINE.md's ">=50x over single-process Meili").
2. **Oracle.** An implementation independent of lax.scan/associative-scan
   for the equivalence tests.

Semantics mirror matcher/hmm.py exactly: emission ``-0.5*(d/sigma)^2``
(invalid candidates -inf), transition ``-|route-gc|/beta`` (unreachable
-inf), SKIP steps carry state through the identity, RESTART steps start a
new chain carrying the finished chain's best score as a constant offset.
"""
from __future__ import annotations

import numpy as np

from .hmm import NEG_INF, RESTART, SKIP, UNREACHABLE_THRESHOLD


def viterbi_decode_numpy(dist_m, valid, route_m, gc_m, case, sigma, beta):
    """Decode ONE trace; shapes (T,K), (T,K), (T-1,K,K), (T-1,), (T,).

    Returns (path (T,) i32, score f32) with the same contract as one row
    of hmm.viterbi_decode_batch.
    """
    dist_m = np.asarray(dist_m, dtype=np.float32)
    route_m = np.asarray(route_m, dtype=np.float32)
    gc_m = np.asarray(gc_m, dtype=np.float32)
    case = np.asarray(case)
    T, K = dist_m.shape

    em = np.where(valid, -0.5 * (dist_m / np.float32(sigma)) ** 2, NEG_INF)
    em[case == SKIP] = 0.0

    identity = np.where(np.eye(K, dtype=bool), 0.0, NEG_INF).astype(np.float32)

    scores = em[0].copy()
    bps = np.empty((T - 1, K), dtype=np.int32)
    prev_bests = np.empty(T - 1, dtype=np.int32)
    for t in range(1, T):
        if case[t] == SKIP:
            tr_t = identity
        elif case[t] == RESTART:
            tr_t = np.zeros((K, K), dtype=np.float32)
        else:
            dev = np.abs(route_m[t - 1] - gc_m[t - 1])
            tr_t = np.where(route_m[t - 1] < UNREACHABLE_THRESHOLD,
                            -dev / np.float32(beta), NEG_INF)
        cand = scores[:, None] + tr_t
        best = cand.max(axis=0)
        bps[t - 1] = cand.argmax(axis=0)
        prev_bests[t - 1] = int(scores.argmax())
        stepped = best + em[t]
        if case[t] == RESTART:
            scores = scores.max() + em[t]
        else:
            scores = stepped

    path = np.empty(T, dtype=np.int32)
    path[-1] = int(scores.argmax())
    for t in range(T - 1, 0, -1):
        if case[t] == RESTART:
            path[t - 1] = prev_bests[t - 1]
        else:
            path[t - 1] = bps[t - 1][path[t]]
    return path, np.float32(scores.max())


def viterbi_decode_numpy_batch(dist_m, valid, route_m, gc_m, case, sigma,
                               beta):
    """Row-vectorised twin of :func:`viterbi_decode_numpy`: shapes
    (B,T,K), (B,T,K), (B,T-1,K,K), (B,T-1), (B,T); returns (paths (B,T)
    i32, scores (B,) f32), each row identical to the per-trace oracle
    (same float32 expressions, same first-index argmax ties). One numpy
    op per time step for the whole chunk instead of per trace — the
    shadow sampler's oracle, cheap enough to keep up with a chip."""
    dist_m = np.asarray(dist_m, dtype=np.float32)
    route_m = np.asarray(route_m, dtype=np.float32)
    gc_m = np.asarray(gc_m, dtype=np.float32)
    case = np.asarray(case)
    B, T, K = dist_m.shape

    em = np.where(valid, -0.5 * (dist_m / np.float32(sigma)) ** 2, NEG_INF)
    em[case == SKIP] = 0.0
    identity = np.where(np.eye(K, dtype=bool), 0.0, NEG_INF).astype(np.float32)

    # every step's transition matrix at once (the same float32
    # expressions the per-trace loop evaluates one step at a time)
    with np.errstate(invalid="ignore"):  # SKIP rows' routes are unused
        dev = np.abs(route_m - gc_m[:, :, None, None])
        tr = np.where(route_m < UNREACHABLE_THRESHOLD,
                      -dev / np.float32(beta), NEG_INF)
    c = case[:, 1:, None, None]
    tr = np.where(c == SKIP, identity,
                  np.where(c == RESTART, np.float32(0.0), tr))
    restart = case == RESTART

    scores = em[:, 0].copy()
    bps = np.empty((B, T - 1, K), dtype=np.int32)
    prev_bests = np.empty((B, T - 1), dtype=np.int32)
    for t in range(1, T):
        cand = scores[:, :, None] + tr[:, t - 1]
        bp = cand.argmax(axis=1)
        bps[:, t - 1] = bp
        prev_bests[:, t - 1] = scores.argmax(axis=1)
        best = np.take_along_axis(cand, bp[:, None, :], axis=1)[:, 0]
        scores = np.where(restart[:, t, None],
                          scores.max(axis=1, keepdims=True) + em[:, t],
                          best + em[:, t])

    rows = np.arange(B)
    path = np.empty((B, T), dtype=np.int32)
    path[:, -1] = scores.argmax(axis=1)
    for t in range(T - 1, 0, -1):
        path[:, t - 1] = np.where(case[:, t] == RESTART, prev_bests[:, t - 1],
                                  bps[rows, t - 1, path[:, t]])
    return path, scores.max(axis=1).astype(np.float32)
