"""The carried-state advance of the streaming worker
(``ops.incremental_step_batch``, which the incremental matcher looks up
at each dispatch): what its calls are counted by, the device modules
that run it, and the work one call needs.

One call advances N traces by up to R appended kept points, one round
after another: per trace and round, a K x K add of the carried scores
to the transitions and a max over the previous candidates; each round's
distances, mask, route matrix, great-circle distance, case code and
live flag read once, its backpointers and restart anchors written once,
and the carried scores read and written once a call. The padded rows
and rounds of a call are counted: the device does their work too.
"""
from __future__ import annotations

import numpy as np

#: (module, attribute) the kernel spy wraps
TARGET = ("reporter_tpu.ops", "incremental_step_batch")
#: the device modules (``XLA Modules`` names, searched) that run it
MODULES = r"incremental"


def shape(dist_m, *_a, **_kw) -> tuple:
    """(R, N, K, dtype of the distances) of one call."""
    R, N, K = (int(x) for x in np.shape(dist_m))
    return R, N, K, str(np.asarray(dist_m).dtype)


def work(R: int, N: int, K: int, dtype) -> tuple:
    """(operations, bytes) of R rounds of N traces at K candidates."""
    w = int(np.dtype(dtype).itemsize)
    ops = 2 * R * N * K * K
    read = R * (N * K * w          # point -> candidate distances
                + N * K            # candidate mask (bool)
                + N * K * K * w    # route distances from the previous point
                + N * 9)           # great-circle distance, case, live flag
    written = R * (N * K * 4 + N * 4)   # backpointers, restart anchors
    carried = 2 * N * K * 4              # scores in and out (float32)
    return ops, read + written + carried
