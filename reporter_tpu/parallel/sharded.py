"""Sharded batched decode: pjit over the decode mesh.

Two mesh shapes, two contracts:

- the 1-D ``("data",)`` mesh (the serving default, parallel/mesh.py
  ``decode_mesh``): pure batch parallelism — every tensor shards along
  its leading batch axis, params replicate, and NO collective runs in
  the decode, so every backend shards, including the sequential scan.
  Each device runs the identical per-row program it would run alone,
  which is why the sharded scan decode is *bit-identical* to the
  single-device scan decode (the contract tests/test_sharded_decode.py
  pins at 1/2/8 forced host devices).
- the 2-D ``(data, seq)`` mesh (REPORTER_TPU_SEQ_SHARDS > 1): time
  additionally shards along ``seq`` and XLA's GSPMD partitioner inserts
  the max-plus scan's cross-shard combines over ICI — associative
  backend only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.assoc_viterbi import viterbi_assoc_batch


def shard_batch(mesh: Mesh, dist_m, valid, route_m, gc_m, case):
    """Device-put one padded batch with (data, seq) shardings.

    The batch axis must divide the ``data`` mesh axis and T the ``seq``
    axis (callers pad batches/buckets to multiples — batchpad's
    ``pad_batch_to`` exists for this).

    ``route_m`` is the dominant tensor by a factor of K (B, T-1, K, K);
    its ragged T-1 time axis is padded to T with one dead trailing step
    so it shards along ``seq`` like everything else — per-device bytes
    and h2d for the largest input drop by the seq factor (the round-3
    weakness: it used to replicate along seq). The dead step is sliced
    off inside the jitted decode and never scored.
    """
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    B, Tm1 = route_m.shape[0], route_m.shape[1]
    T = dist_m.shape[1]
    if Tm1 == T - 1:
        route_m = np.concatenate(
            [route_m, np.zeros((B, 1) + route_m.shape[2:],
                               dtype=route_m.dtype)], axis=1)
        gc_m = np.concatenate(
            [gc_m, np.zeros((B, 1), dtype=gc_m.dtype)], axis=1)

    return (
        put(dist_m, P("data", "seq", None)),
        put(valid, P("data", "seq", None)),
        put(route_m, P("data", "seq", None, None)),
        put(gc_m, P("data", "seq")),
        put(case, P("data", "seq")),
    )


def shard_batch_data(mesh: Mesh, dist_m, valid, route_m, gc_m, case):
    """Device-put one padded chunk onto a 1-D ``("data",)`` mesh: every
    tensor shards along its leading batch axis (which must divide the
    mesh size — callers pad rows to a multiple, counted in the
    ``padded_cells`` wide event), emission/transition params replicate
    inside the jitted call. No time-axis padding is needed: route's
    ragged T-1 rows only matter to ``seq`` sharding."""
    def put(x):
        spec = P("data", *([None] * (np.ndim(x) - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return (put(dist_m), put(valid), put(route_m), put(gc_m), put(case))


def data_decode_jit(mesh: Mesh, kernel):
    """The jitted data-parallel decode :func:`sharded_data_viterbi`
    runs: (B, T) paths and (B,) scores stay sharded along ``data``."""
    out_sharding = (NamedSharding(mesh, P("data")),
                    NamedSharding(mesh, P("data")))
    return jax.jit(kernel, out_shardings=out_sharding)


def sharded_data_viterbi(mesh: Mesh, kernel):
    """A decode callable running ``kernel`` (an unjitted batch decode —
    scan or assoc) data-parallel over a 1-D ``("data",)`` mesh, with
    sharded in/out specs so the (B, T) paths stay device-sharded until
    the drain lane's d2h gather."""
    decode = data_decode_jit(mesh, kernel)

    def run(dist_m, valid, route_m, gc_m, case, sigma, beta):
        args = shard_batch_data(mesh, dist_m, valid, route_m, gc_m, case)
        return decode(*args, jnp.float32(sigma), jnp.float32(beta))

    return run


def sharded_viterbi(mesh: Mesh):
    """Return a decode callable fixed to ``mesh``.

    out_shardings keep paths on ``data`` so the host gathers only (B, T)
    int32 — the K-width intermediates never leave the devices.
    """
    out_sharding = (NamedSharding(mesh, P("data", "seq")),
                    NamedSharding(mesh, P("data")))

    # route/gc arrive padded to T time rows (dead trailing step) so they
    # shard along seq; the kernel itself sheds the dead step inside jit
    # (matcher/hmm.py trim_time_pad) and GSPMD partitions the slice
    decode = jax.jit(viterbi_assoc_batch.__wrapped__,
                     out_shardings=out_sharding)

    def run(dist_m, valid, route_m, gc_m, case, sigma, beta):
        args = shard_batch(mesh, dist_m, valid, route_m, gc_m, case)
        return decode(*args, jnp.float32(sigma), jnp.float32(beta))

    return run
