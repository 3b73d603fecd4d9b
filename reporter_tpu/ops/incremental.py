"""Batched incremental Viterbi steps: advance N carried traces by their
appended points.

The windowed decode (``matcher/hmm.py``) re-runs the full ``lax.scan``
over T kept points every time a window is matched — for a long-lived
streaming uuid that is O(T·K^2) per report forever (ISSUE 19; the batcher
trims only the consumed prefix, so windows overlap). This kernel is the
device half of the incremental path: it advances the carried per-trace
decode state — last-step log-scores (K,) per trace — by the appended
kept points, for N active traces and up to R points each in a single
dispatch (each dispatch is a host round trip, which costs far more than
the O(N·K^2) work of a step).

One step of ``_viterbi_single``'s forward scan, vmapped over traces,
and up to R such steps in one dispatch (a ``lax.scan`` over rounds):

  cand       = prev_scores[:, None] + tr          # (K_prev, K_cur)
  best, bp   = max/argmax over K_prev
  new_scores = where(case == RESTART, max(prev_scores) + em, best + em)
  prev_best  = argmax(prev_scores)                # restart backtrace anchor

Emission/transition scoring reuses ``emission_scores`` /
``transition_scores`` verbatim (time axis of length 1), so RESTART /
SKIP / unreachable semantics are *definitionally* identical to the batch
kernel — and because the only reductions involved are max/argmax (exact
in f32, order-independent) and the adds are elementwise, the scores this
step produces are bit-identical to the same step inside the batch scan.
That equivalence is what lets the windowed decode serve as the byte-exact
parity oracle for the whole incremental path (tests/test_incremental.py).

The ``live`` mask makes the batch ragged: a trace that has no appended
point in a round keeps its scores through it, and the host discards its
outputs there.

Backpointers of every round return to the host; the host keeps the bounded
(L, K) ring and owns fixed-lag commit (matcher/incremental.py) — the
ring is pure integer bookkeeping, O(K) a step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..matcher.hmm import RESTART, emission_scores, transition_scores

__all__ = ["incremental_step_batch"]


def _advance(d, v, r, g, c, prev, sigma, beta):
    """One trace's step: (new scores (K,), backpointers (K,), restart
    anchor)."""
    em = emission_scores(d[None], v[None], c[None], sigma)[0]       # (K,)
    tr = transition_scores(r[None], g[None], c[None], beta)[0]      # (K,K)
    cand = prev[:, None] + tr
    best = jnp.max(cand, axis=0)
    bp = jnp.argmax(cand, axis=0).astype(jnp.int32)
    stepped = best + em
    restarted = jnp.max(prev) + em
    new_scores = jnp.where(c == RESTART, restarted, stepped)
    prev_best = jnp.argmax(prev).astype(jnp.int32)
    return new_scores, bp, prev_best


@jax.jit
def incremental_step_batch(dist_m: jnp.ndarray, valid: jnp.ndarray,
                           route_m: jnp.ndarray, gc_m: jnp.ndarray,
                           case: jnp.ndarray, live: jnp.ndarray,
                           prev_scores: jnp.ndarray, sigma: jnp.ndarray,
                           beta: jnp.ndarray):
    """Advance N carried traces by up to R appended kept points, one
    round (one point of every live trace) after another.

    Shapes: dist_m (R, N, K) f32/f16 point->edge distances of each
    round's point; valid (R, N, K) bool; route_m (R, N, K, K) f32/f16
    route distances from each trace's previous kept point; gc_m (R, N)
    f32/f16 great-circle distances; case (R, N) i32 case codes; live
    (R, N) bool, whether the trace has a point in the round (a trace
    without one keeps its scores and its outputs there are discarded);
    prev_scores (N, K) f32 carried last-step log-scores; sigma, beta
    scalars. Returns (scores (N, K) f32 after the last round,
    bp (R, N, K) i32 backpointers, prev_best (R, N) i32 restart
    backtrace anchors).

    A window's FIRST kept point is a step with case=RESTART from
    prev_scores=0: ``max(0) + em == em``, exactly the scan's ``init``.
    """
    def one_round(prev, xs):
        d, v, r, g, c, lv = xs
        new, bp, anchor = jax.vmap(
            lambda *a: _advance(*a, sigma, beta))(d, v, r, g, c, prev)
        return jnp.where(lv[:, None], new, prev), (bp, anchor)

    scores, (bp, prev_best) = jax.lax.scan(
        one_round, prev_scores, (dist_m, valid, route_m, gc_m, case, live))
    return scores, bp, prev_best
