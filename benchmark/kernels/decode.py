"""The batched Viterbi decode of ``/report`` (``ops.decode_batch``, which
the matcher looks up at each call): what its calls are counted by, the
device modules that run it, and the work its recurrence needs.

The count is of what the recurrence needs, whichever backend runs it
(``scan``, ``assoc`` or ``pallas``): per step, a K x K add and max over
the candidates; the emission, transition, great-circle and case tensors
read once, and the path written once. A backend that does more work
(``assoc``'s O(K^3) max-plus products) or moves more bytes reads as a
lower share of its roofline, never as more work.
"""
from __future__ import annotations

import numpy as np

#: (module, attribute) the kernel spy wraps
TARGET = ("reporter_tpu.ops", "decode_batch")
#: the device modules (``XLA Modules`` names, searched) that run it
MODULES = r"viterbi"


def shape(dist_m, *_a, **_kw) -> tuple:
    """(B, T, K, wire dtype) of one call."""
    B, T, K = (int(x) for x in dist_m.shape)
    return B, T, K, str(dist_m.dtype)


def work(B: int, T: int, K: int, wire) -> tuple:
    """(operations, bytes) of one batched decode of B traces, T points,
    K candidates, distances on the wire in ``wire``."""
    w = int(np.dtype(wire).itemsize)
    steps = B * max(T - 1, 0)
    ops = 2 * steps * K * K
    read = (B * T * K * w          # point -> candidate distances
            + B * T * K            # candidate mask (bool)
            + steps * K * K * w    # route distances, one K x K per step
            + steps * w            # great-circle distances
            + B * T * 4)           # case codes (int32)
    written = B * T * 4            # the path (int32)
    return ops, read + written


def plant(fault: str, args: tuple, _kw: dict, out) -> tuple:
    """A decode answer broken as ``fault`` says (``--fault``, for the
    tests that show the check fails such a run):

    - ``answer``: every point's candidate moved to the next valid one,
      an answer altered where it is produced;
    - ``half_batch``: the second half of the batch's rows left out, each
      point on its first candidate."""
    paths, scores = out
    p = np.array(paths)
    if fault == "answer":
        v = np.asarray(args[1])
        alt = (p + 1) % v.shape[2]
        ok = np.take_along_axis(v, alt[..., None], axis=2)[..., 0]
        p = np.where(ok, alt, p)
    elif fault == "half_batch":
        p[p.shape[0] // 2:] = 0
    return p.astype(np.int32), scores
