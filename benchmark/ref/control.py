"""The control of the correctness check: the reference decode in bfloat16.

The configuration states float16 distances on the wire and float32
scores (``matcher/hmm.py`` scores in float32 whatever the wire dtype).
The control takes each one step down, the steps a later PR might take to
halve the decode's bytes: the reference pipeline ships its distances as
8-bit floats (``pipeline.WIRE_DTYPES["fp8"]``) and decodes with this,
``viterbi.viterbi_decode_numpy`` with every score (emission, transition,
running sum) rounded to bfloat16. The check has to fail it where the
float32 reference passes.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from .viterbi import NEG_INF, RESTART, SKIP, UNREACHABLE_THRESHOLD

BF16 = ml_dtypes.bfloat16


def _bf(x):
    return np.asarray(x, dtype=np.float32).astype(BF16).astype(np.float32)


def viterbi_decode_bf16(dist_m, valid, route_m, gc_m, case, sigma, beta):
    """``viterbi_decode_numpy`` with scores held in bfloat16."""
    dist_m = np.asarray(dist_m, dtype=np.float32)
    route_m = np.asarray(route_m, dtype=np.float32)
    gc_m = np.asarray(gc_m, dtype=np.float32)
    case = np.asarray(case)
    T, K = dist_m.shape

    em = np.where(valid, -0.5 * (dist_m / np.float32(sigma)) ** 2, NEG_INF)
    em[case == SKIP] = 0.0
    em = _bf(em)
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
            tr_t = _bf(np.where(route_m[t - 1] < UNREACHABLE_THRESHOLD,
                                -dev / np.float32(beta), NEG_INF))
        cand = _bf(scores[:, None] + tr_t)
        best = cand.max(axis=0)
        bps[t - 1] = cand.argmax(axis=0)
        prev_bests[t - 1] = int(scores.argmax())
        if case[t] == RESTART:
            scores = _bf(scores.max() + em[t])
        else:
            scores = _bf(best + em[t])

    path = np.empty(T, dtype=np.int32)
    path[-1] = int(scores.argmax())
    for t in range(T - 1, 0, -1):
        if case[t] == RESTART:
            path[t - 1] = prev_bests[t - 1]
        else:
            path[t - 1] = bps[t - 1][path[t]]
    return path, np.float32(scores.max())
