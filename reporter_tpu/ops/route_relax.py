"""Device route kernels: batched bounded relaxation + pair-cost assembly.

The transition-cost stage used to be the host's job: one bounded Dijkstra
per (candidate-edge, candidate-edge) pair miss, fanned across C++ threads
(native/src/host_runtime.cpp route_step). BENCH_DEV_r09 measured that
stage — ``prep_routes`` — as the pipeline's dominant line item. These two
jitted kernels move it onto the device:

``relax_csr``
    One padded multi-source bounded relaxation over the road graph's edge
    columns (the same CSR-backing arrays the native runtime loads): a
    Bellman-Ford-style gather/scatter sweep that settles, for every
    source node in the chunk at once, the exact shortest network distance
    to every node within ``bound`` meters — and the travel time *along
    that shortest-distance path* (time rides along, it never drives the
    search, matching Meili and route_step). All arithmetic is float32 in
    path order, mirroring the C++ node kernel (``nd = d + edge_len[e]``,
    ``secs = meters / (max(kph, 1) / 3.6)``), so a settled distance is
    bit-identical to the host Dijkstra's value for the same path.

``pair_costs``
    The vectorised twin of route_step's admissibility emitter: gathers
    the relaxed node kernels into the padded (B, T-1, K, K) route tensor,
    applying the same-edge forward/backward cases, the distance bound
    ``max(min_bound, factor * gc)``, the time cap
    ``max(min_time_bound, time_factor * dt)`` and the turn penalty in the
    exact float32 expression order of the C++ emitter. Padding candidates
    (edge -1) and steps at/after ``num_kept - 1`` emit the UNREACHABLE
    sentinel — identical bytes to what the host path's tail fill writes.

Bound semantics are exactness-safe under batching: the relaxation runs at
the CHUNK-global bound (the max over every live step's bound). A bounded
search at a larger bound settles a superset of exact distances and never
changes a settled value, and ``pair_costs`` re-applies each step's own
bound — so an entry is finite iff the per-pair host search would have
found it, with the same value. Equal-distance ties are the one accepted
divergence: the host Dijkstra keeps the first-settled path's travel time
(heap order), the relaxation keeps the minimum — which can flip a
time-cap verdict only on exact float ties, the same class of divergence
the native/numpy pair already exhibits (and the report-byte parity tests
pin to be inert).

Convergence is explicit: the sweep stops when neither distances nor times
changed (times keep relaxing along the shortest-path DAG after distances
settle, so both must be quiet), or at ``max_iters`` — in which case the
``converged`` flag is False and the caller must fall back to the host
path rather than trust a partially-relaxed tensor.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: the unreachable sentinel, identical to graph/route.py UNREACHABLE and
#: the C++ kUnreachable (1.0e9f)
UNREACHABLE = 1.0e9


@partial(jax.jit, static_argnames=("n_nodes", "max_iters"))
def relax_csr(edge_start, edge_end, edge_len, edge_secs, src_nodes,
              bound, *, n_nodes: int, max_iters: int):
    """Multi-source bounded relaxation over the edge columns.

    Args:
      edge_start, edge_end: (E,) int32 directed edge endpoints.
      edge_len:  (E,) float32 edge lengths, meters.
      edge_secs: (E,) float32 full-edge travel seconds
                 (``edge_len / (max(kph, 1) / 3.6)`` in float32).
      src_nodes: (S,) int32 source node ids (duplicates allowed — padding
                 rows repeat a real source and are simply redundant).
      bound:     float32 scalar; paths whose running distance exceeds it
                 stop relaxing (the chunk-global route bound).
      n_nodes:   static node count N.
      max_iters: static sweep cap (>= longest bounded path in hops + 1).

    Returns ``(dist, time, iters, converged)``: (S, N) float32 exact
    bounded shortest distances (inf = not reachable within ``bound``),
    (S, N) float32 travel seconds along those shortest-distance paths
    (min over equal-distance ties), the sweep count actually run, and
    whether the sweep reached a fixpoint before ``max_iters``.
    """
    S = src_nodes.shape[0]
    inf = jnp.float32(jnp.inf)
    rows = jnp.arange(S, dtype=jnp.int32)
    dist0 = jnp.full((S, n_nodes), inf, jnp.float32)
    dist0 = dist0.at[rows, src_nodes].set(jnp.float32(0.0))
    time0 = jnp.full((S, n_nodes), inf, jnp.float32)
    time0 = time0.at[rows, src_nodes].set(jnp.float32(0.0))

    def body(state):
        dist, time, it, _ = state
        # gather: candidate relaxations through every edge at once
        cd = dist[:, edge_start] + edge_len[None, :]
        ct = time[:, edge_start] + edge_secs[None, :]
        ok = cd <= bound  # the Dijkstra admission rule (nd > bound skips)
        cd = jnp.where(ok, cd, inf)
        ct = jnp.where(ok, ct, inf)
        # scatter-min distances (duplicate targets reduce correctly)
        nd = dist.at[:, edge_end].min(cd)
        # lexicographic (d, t): among arcs achieving the (possibly
        # unchanged) new distance at their target, keep the minimum
        # time; nodes whose distance improved reset their time first
        tie = jnp.where(cd == nd[:, edge_end], ct, inf)
        nt = jnp.where(nd == dist, time, inf)
        nt = nt.at[:, edge_end].min(tie)
        changed = jnp.any(nd != dist) | jnp.any(nt != time)
        return nd, nt, it + 1, changed

    def cond(state):
        _, _, it, changed = state
        return changed & (it < max_iters)

    dist, time, iters, changed = jax.lax.while_loop(
        cond, body, (dist0, time0, jnp.int32(0), jnp.bool_(True)))
    return dist, time, iters, jnp.logical_not(changed)


@jax.jit
def pair_costs(edge, offset, nk, bounds, caps, dist_sn, time_sn,
               node_row, edge_start, edge_end, edge_len, edge_v,
               head_x, head_y, backward_tol, turn_penalty_factor):
    """Assemble the (B, T-1, K, K) route tensor from relaxed kernels.

    Args:
      edge, offset: (B, T, K) int32 / float32 candidate tensors (pad -1).
      nk:        (B,) int32 kept point counts (steps >= nk-1 are dead).
      bounds:    (B, T-1) float32 per-step distance bound.
      caps:      (B, T-1) float32 per-step time cap; < 0 disables it.
      dist_sn, time_sn: (S, N) float32 relaxed node kernels.
      node_row:  (N,) int32 node id -> relaxation row (-1 = not a source).
      edge_start, edge_end: (E,) int32; edge_len (E,) float32.
      edge_v:    (E,) float32 edge speed in m/s (``max(kph, 1) / 3.6``).
      head_x, head_y: (E,) float32 unit headings (turn penalty).
      backward_tol, turn_penalty_factor: float32 scalars.

    Returns ``(route, max_finite)``: the route tensor (UNREACHABLE where
    inadmissible / padded / dead) and the largest finite cost written
    (0 when none) — the wire-dtype decision input.
    """
    unreach = jnp.float32(UNREACHABLE)
    ea = edge[:, :-1, :][..., :, None]       # (B, T-1, K, 1)
    eb = edge[:, 1:, :][..., None, :]        # (B, T-1, 1, K)
    oa = offset[:, :-1, :][..., :, None]
    ob = offset[:, 1:, :][..., None, :]
    sa = jnp.maximum(ea, 0)
    sb = jnp.maximum(eb, 0)

    remaining = edge_len[sa] - oa            # (B, T-1, K, 1)
    via = remaining + ob                     # (B, T-1, K, K)
    row = node_row[edge_end[sa]]             # (B, T-1, K, 1)
    dn = dist_sn[jnp.maximum(row, 0), edge_start[sb]]
    tn = time_sn[jnp.maximum(row, 0), edge_start[sb]]

    b_ = bounds[:, :, None, None]
    cap = caps[:, :, None, None]
    via_dn = via + dn
    # general pair: the emit() ladder of route_step, in its order
    bad = (via > b_) | (row < 0) | jnp.logical_not(jnp.isfinite(dn)) \
        | (via_dn > b_)
    secs = remaining / edge_v[sa] + ob / edge_v[sb] + tn
    bad = bad | ((cap >= 0) & (secs > cap))
    cos_th = head_x[sa] * head_x[sb] + head_y[sa] * head_y[sb]
    pen = (turn_penalty_factor * jnp.float32(0.5)) \
        * (jnp.float32(1.0) - cos_th)
    d_gen = jnp.where(turn_penalty_factor > 0, via_dn + pen, via_dn)
    general = jnp.where(bad, unreach, d_gen)

    # same directed edge: forward progress prices the along-edge meters
    # (time-capped); small apparent backward motion prices as staying put
    same = eb == ea
    fwd = same & (ob >= oa)
    d_fwd = ob - oa
    fwd_val = jnp.where((cap >= 0) & (d_fwd / edge_v[sa] > cap),
                        unreach, d_fwd)
    back = same & (ob < oa) & ((oa - ob) <= backward_tol)
    val = jnp.where(fwd, fwd_val,
                    jnp.where(back, jnp.float32(0.0), general))

    steps = jnp.arange(edge.shape[1] - 1, dtype=nk.dtype)
    dead = (ea < 0) | (eb < 0) \
        | (steps[None, :, None, None] >= (nk[:, None, None, None] - 1))
    out = jnp.where(dead, unreach, val)
    max_finite = jnp.max(jnp.where(out < unreach, out, jnp.float32(0.0)),
                         initial=jnp.float32(0.0))
    return out, max_finite


@partial(jax.jit, static_argnames=("B", "T", "K", "N"))
def pair_costs_packed(ints, f32s, dist_sn, time_sn, row_lo, prev,
                      edge_start, edge_end, edge_len, edge_v,
                      head_x, head_y, *, B, T, K, N):
    """pair_costs with the six small per-chunk tensors packed into two
    1-D blobs so a warm dispatch pays two host->device transfers
    instead of eight. Pure repacking — slices/reshapes inside the jit
    are free and the assembled bytes match pair_costs exactly.

    ``dist_sn``/``time_sn`` hold relaxation rows ``[row_lo, row_lo +
    S)`` of the chunk's sources: pairs whose source row lies outside
    them price as UNREACHABLE, and the result is merged into ``prev``
    (the (B, T-1, K, K) tensor of the blocks before, all UNREACHABLE
    for the first) by elementwise minimum — each pair takes its value
    from its own block, the others never exceed UNREACHABLE. One
    program per (B, T), whatever the chunk's source count; the returned
    finite max covers every block merged so far.

    Layouts (see DeviceRouteKernel._run, the only caller):
      ints: [edge (B*T*K) | nk (B) | node_row (N)]            int32
      f32s: [offset (B*T*K) | bounds (B*(T-1)) | caps (B*(T-1))
             | backward_tol | turn_penalty_factor]            float32
    """
    btk = B * T * K
    edge = ints[:btk].reshape(B, T, K)
    nk = ints[btk:btk + B]
    row = ints[btk + B:btk + B + N] - row_lo
    node_row = jnp.where((row >= 0) & (row < dist_sn.shape[0]), row, -1)
    offset = f32s[:btk].reshape(B, T, K)
    bt1 = B * (T - 1)
    bounds = f32s[btk:btk + bt1].reshape(B, T - 1)
    caps = f32s[btk + bt1:btk + 2 * bt1].reshape(B, T - 1)
    backward_tol = f32s[btk + 2 * bt1]
    turn_penalty_factor = f32s[btk + 2 * bt1 + 1]
    route, _ = pair_costs(edge, offset, nk, bounds, caps, dist_sn,
                          time_sn, node_row, edge_start, edge_end,
                          edge_len, edge_v, head_x, head_y, backward_tol,
                          turn_penalty_factor)
    out = jnp.minimum(prev, route)
    unreach = jnp.float32(UNREACHABLE)
    max_finite = jnp.max(jnp.where(out < unreach, out, jnp.float32(0.0)),
                         initial=jnp.float32(0.0))
    return out, max_finite
