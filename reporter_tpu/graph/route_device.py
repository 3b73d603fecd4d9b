"""DeviceRouteKernel: the chunk-batched device route-cost stage.

Owns the device-resident graph columns for one :class:`RoadNetwork` and
turns a native-prepared chunk's candidate tensors into its (B, T-1, K, K)
route tensor with ONE multi-source bounded relaxation + ONE gather/
scatter assembly (ops/route_relax.py) instead of per-pair host Dijkstra
searches. The host path (graph/route.py, native route_step) stays the
byte-identical fallback and parity oracle; the matcher guards this path
with its own circuit domain (``route.device``) and re-runs the native
prep with routes on any failure here, so a broken device can never
change report bytes.

Per chunk the kernel:

1. collects the live candidate edges' end nodes (the relaxation
   sources), deduplicated and padded to whole blocks;
2. relaxes them all at the chunk-global bound — the max over every live
   step's ``max(min_bound, factor * gc)`` — which is exactness-safe: a
   bounded search at a larger bound settles a superset of the same exact
   distances, and the assembly re-applies each step's own bound;
3. assembles the route tensor over every prepared row, filler rows
   included (so its compiled shape follows the chunk's planned rows
   and T, not its trace count), and writes it into the prep dict's ``route_m``
   time rows ``[:T-1]`` (row T-1 is the dead trailing step the native
   tail fill already covered), folding the device finite max into
   ``max_finite`` so the f16 wire decision sees device-written values.

A relaxation that fails to converge within the sweep cap raises instead
of returning a partially-relaxed tensor; so does a graph on which not
one source's sweep fits the working-set budget — both are ordinary
circuit failures to the caller. On a metro graph one sweep's (sources x
edges) working set outgrows its budget at a few hundred sources (a
32-trace chunk on 22,500 nodes has ~900), so without the cache the
sources relax in fixed-size blocks — one compiled relaxation and one
compiled assembly per bucket, whatever the chunk's source count — each
block assembled on its own and the results merged by elementwise
minimum.

On small graphs (``2 * N * N`` float32 elements within the cache
budget) the kernel keeps a device-resident node-kernel cache: one
(N, N) distance/time row pair per relaxed source node, tagged with the
bound it was relaxed at. A row relaxed at bound ``b`` is EXACT for any
query bound ``<= b`` (every admissible path's prefixes are admissible,
so the settled values — and the equal-distance tie set the time min
runs over — are identical), which is the same monotone-bound reuse rule
the host RouteCache applies. Steady-state chunks over a warm city then
skip the relaxation entirely and run only the gather/scatter assembly —
the fill drops from O(sweeps x E x S) to O(pairs). Rows are committed
only after a converged sweep, so a fallback chunk never poisons the
cache.

The per-city ``.profile`` artifact (datastore/profile.py) can carry the
observed ``route_hops``/``route_bound_m`` of a serving run; ``seed_hint``
consumes them so a freshly warmed city starts with a tight sweep cap
instead of the worst-case node count.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from ..utils import faults, metrics
from .network import RoadNetwork
from .route import UNREACHABLE

#: sweep cap override; 0/unset = auto (profile hint or the node count)
ENV_HOPS = "REPORTER_TPU_ROUTE_HOPS"

#: ceiling on ONE relaxation block's working set (sources x max(nodes,
#: edges) float32 elements, two states); sources beyond it relax in
#: further blocks. 64M elements = 256 MB.
_STATE_BUDGET_ELEMS = 64 * 1024 * 1024

#: most sources per relaxation block: a chunk's source count varies, the
#: block does not (one compiled relaxation per graph), so a small chunk
#: pads at most this many rows
_BLOCK_SOURCES_MAX = 256

#: ceiling on the dense (nodes x nodes) node-kernel cache (two float32
#: states); graphs over it (N > ~2.8k nodes) serve uncached, per-chunk.
_CACHE_BUDGET_ELEMS = 16 * 1024 * 1024


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class DeferredRoutes:
    """A chunk's dispatched-but-unsynced device route tensor.

    ``fill_prep(defer=True)`` returns one of these instead of blocking on
    the device→host copy: ``route`` is the in-flight (B, T-1, K, K)
    float32 array, ``max_finite`` its finite-max scalar. The prep stage
    stays dispatch-only; the first consumer that needs host bytes (the
    decode stage's wire finalisation, the lazy per-trace views) calls
    :meth:`write_back`, which blocks there — overlapping the device
    assembly with the next chunk's native prep. Every failure mode
    (budget, non-convergence, faults) still raises at dispatch time
    inside ``fill_prep``, so the caller's circuit semantics are
    unchanged; the assembly itself is pure arithmetic.

    On a fully warm node-kernel cache even the *dispatch* (blob packing
    + two transfers + the jit call) leaves the prep thread: ``fut`` is
    then a future resolving to ``(route, max_finite)``, submitted to the
    kernel's single dispatch worker. That path has no relax, hence no
    convergence check — nothing left that the circuit needs to see at
    prep time."""

    __slots__ = ("route", "max_finite", "_B", "_T", "_lock", "_done",
                 "_fut")

    def __init__(self, route, max_finite, B: int, T: int, fut=None):
        import threading
        self.route = route
        self.max_finite = max_finite
        self._B = B
        self._T = T
        self._lock = threading.RLock()  # write_back resolves under it
        self._done = False
        self._fut = fut

    def resolve(self):
        """Block until the device arrays are in hand (idempotent);
        returns ``(route, max_finite)`` — still device-resident."""
        with self._lock:
            if self._fut is not None:
                self.route, self.max_finite = self._fut.result()
                self._fut = None
            return self.route, self.max_finite

    def write_back(self, out: dict) -> None:
        """Materialise into the prep dict (idempotent, thread-safe):
        route bytes into ``route_m[:B, :T-1]``, finite max folded into
        ``max_finite`` — byte-identical to the non-deferred path."""
        with self._lock:
            if self._done:
                return
            route, dev_max = self.resolve()
            out["route_m"][:self._B, :self._T - 1] = np.asarray(route)
            out["max_finite"][0] = max(float(out["max_finite"][0]),
                                       float(dev_max))
            self._done = True


class DeviceRouteKernel:
    """Batched device route costs for one road network."""

    def __init__(self, net: RoadNetwork):
        import jax.numpy as jnp  # deferred: graph/ stays numpy-importable

        self.net = net
        self.n_nodes = int(net.num_nodes)
        self.n_edges = int(net.num_edges)
        # float32 edge columns in the C++ runtime's exact arithmetic:
        # m/s = max(kph, 1) * (1/3.6) as float32, secs = meters / v
        speed = np.asarray(net.edge_speed_kph, dtype=np.float32)
        v = np.maximum(speed, np.float32(1.0)) \
            * (np.float32(1.0) / np.float32(3.6))
        e_len = np.asarray(net.edge_length_m, dtype=np.float32)
        heads = np.asarray(net.headings(), dtype=np.float32)
        self._e_start = jnp.asarray(net.edge_start.astype(np.int32))
        self._e_end = jnp.asarray(net.edge_end.astype(np.int32))
        self._e_len = jnp.asarray(e_len)
        self._e_v = jnp.asarray(v)
        self._e_secs = jnp.asarray(e_len / v)
        self._head_x = jnp.asarray(heads[:, 0])
        self._head_y = jnp.asarray(heads[:, 1])
        # host copy for source gathering (no device round-trip per chunk)
        self._end_np = np.asarray(net.edge_end, dtype=np.int32)
        # sweep-cap seed (profile hint) + observed stats for export
        self._hops_hint = 0
        self.max_iters_seen = 0
        self.max_bound_seen = 0.0
        # device-resident node-kernel cache (see module docstring):
        # (N, N) relaxed rows, row i = source node i, valid while
        # _row_bound[i] >= the query bound; -1 = never relaxed
        self._cache_ok = 2 * self.n_nodes * self.n_nodes \
            <= _CACHE_BUDGET_ELEMS
        self._cache_dist = None
        self._cache_time = None
        self._row_bound = np.full(self.n_nodes, -1.0, dtype=np.float32)
        self._pool = None  # lazy: see _dispatch_pool()

    # -- profile plumbing --------------------------------------------------
    def seed_hint(self, route_hops: int) -> None:
        """Seed the sweep cap from a committed ``.profile`` artifact's
        observed hop count (datastore/profile.py warm_matcher)."""
        if route_hops > 0:
            self._hops_hint = int(route_hops)

    def stats(self) -> dict:
        """Observed relaxation stats for the profile export."""
        return {"route_hops": int(self.max_iters_seen),
                "route_bound_m": float(self.max_bound_seen)}

    def _iter_cap(self) -> int:
        raw = os.environ.get(ENV_HOPS, "").strip()
        if raw:
            try:
                forced = int(raw)
                if forced > 0:
                    return forced
            except ValueError:
                import logging
                logging.getLogger("reporter_tpu.graph").warning(
                    "%s=%r not an integer; using the auto cap",
                    ENV_HOPS, raw)
        if self._hops_hint > 0:
            # headroom over the recorded depth: a trace family slightly
            # deeper than the profile's still converges (and re-records)
            return max(self._hops_hint * 2, 16)
        return max(self.n_nodes, 2)

    # -- the chunk hot path ------------------------------------------------
    def fill_prep(self, out: dict, params,
                  min_bound_m: float = 500.0,
                  defer: bool = False) -> "Optional[DeferredRoutes]":
        """Compute and write ``out['route_m'][:, :T-1]`` for a native
        ``prepare_batch(..., skip_routes=True)`` result dict, updating
        ``out['max_finite']``. Every row is assembled, filler rows too
        (all dead: the same UNREACHABLE bytes the native tail fill
        writes), so the compiled shape is the chunk's planned one.
        Raises on non-convergence or a budget-exceeding chunk (the
        caller's circuit fallback re-runs the native prep with routes).

        ``defer=True`` skips the device→host sync: the assembly is
        dispatched and a :class:`DeferredRoutes` handle returned (None
        when the chunk had nothing to route and the prep dict is already
        complete). All circuit-visible failure modes (budget, faults,
        relax non-convergence) still raise HERE: on a fully warm cache
        — the only case where the dispatch itself is handed to the
        background worker — no relax runs, so nothing checkable is
        deferred past this frame."""
        faults.failpoint("route.device")
        edge = np.asarray(out["edge_ids"])
        B, T = edge.shape[:2]
        if T < 2:
            return
        nk = np.asarray(out["num_kept"])
        gc = np.asarray(out["gc_m"][:, :T - 1])
        dt = np.asarray(out["dt"][:, :T - 1])

        # per-step bounds/caps in the C++ double->float32 expression
        bounds = np.maximum(
            np.float64(min_bound_m),
            np.float64(params.max_route_distance_factor)
            * gc.astype(np.float64)).astype(np.float32)
        tf = float(params.max_route_time_factor)
        caps = np.where(
            (tf > 0) & (dt > 0),
            np.maximum(np.float64(params.min_time_bound_s),
                       np.float64(tf) * dt),
            np.float64(-1.0)).astype(np.float32)

        steps = np.arange(T - 1)
        live_step = steps[None, :] < (nk[:, None] - 1)
        ea_live = live_step[:, :, None] & (edge[:, :T - 1, :] >= 0)
        if not bool(ea_live.any()):
            # no live transitions anywhere: the native tail fill already
            # wrote every route row of these traces
            metrics.count("route.device.empty_chunks")
            return
        chunk_bound = np.float32(bounds[live_step].max())

        # unique source nodes via a flag scan over the node-id space:
        # O(pairs + N) with no sort (np.unique was the costliest host
        # op left on the warm path), same sorted result
        flags = np.zeros(self.n_nodes, dtype=bool)
        flags[self._end_np[edge[:, :T - 1, :][ea_live]]] = True
        srcs = np.flatnonzero(flags).astype(np.int32)
        if self._block_sources() < 1:
            metrics.count("route.device.budget_exceeded")
            raise RuntimeError(
                f"route relax state over budget: {len(srcs)} sources x "
                f"{self.n_nodes} nodes")
        btol = float(params.backward_tolerance_m)
        tpen = float(params.turn_penalty_factor)
        offset = np.asarray(out["offset_m"])
        metrics.count("route.device.chunks")
        metrics.count("route.device.pairs",
                      int(ea_live.sum()) * edge.shape[2])
        metrics.count("route.device.sources", int(len(srcs)))
        if (defer and self._cache_ok and self._cache_dist is not None
                and bool(np.all(self._row_bound[srcs] >= chunk_bound))):
            # fully warm cache: no relax, hence no convergence check —
            # nothing left that can raise for circuit purposes, so even
            # the dispatch leaves the prep critical path
            metrics.count("route.device.deferred_chunks")
            metrics.count("route.device.async_dispatch_chunks")
            fut = self._dispatch_pool().submit(
                self._run, edge, offset, nk, bounds, caps, srcs,
                chunk_bound, btol, tpen)
            return DeferredRoutes(None, None, B, T, fut=fut)
        route, dev_max = self._run(edge, offset, nk, bounds, caps, srcs,
                                   chunk_bound, btol, tpen)
        if defer:
            metrics.count("route.device.deferred_chunks")
            return DeferredRoutes(route, dev_max, B, T)
        # synchronous path: materialise through the same declared sync
        # point as the deferred one (registry.SYNC_POINTS write_back) —
        # one d2h site, byte-identical either way
        DeferredRoutes(route, dev_max, B, T).write_back(out)
        return None

    def _block_sources(self) -> int:
        """Sources per relaxation block: the largest power of two whose
        working set fits :data:`_STATE_BUDGET_ELEMS`, at most
        :data:`_BLOCK_SOURCES_MAX` (0 when not even one source fits)."""
        fit = _STATE_BUDGET_ELEMS // (2 * max(self.n_nodes, self.n_edges))
        return min(1 << (fit.bit_length() - 1), _BLOCK_SOURCES_MAX) \
            if fit else 0

    def _relax_blocks(self, srcs: np.ndarray, chunk_bound, block: int):
        """Relax ``srcs`` at ``chunk_bound`` in blocks of ``block``
        sources (the last padded with a duplicate source: redundant, not
        wrong). Yields ``(lo, dist, time)`` per block — the (block, N)
        kernels of padded source rows ``[lo, lo + block)`` — and raises
        on a block that does not converge, before yielding it."""
        import jax
        import jax.numpy as jnp

        from ..ops import route_relax

        n_blocks = -(-len(srcs) // block)
        pad = np.full(n_blocks * block, srcs[0], dtype=np.int32)
        pad[:len(srcs)] = srcs
        mesh = self._mesh()
        sharding = None
        if mesh is not None and block % mesh.devices.size == 0:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(mesh, PartitionSpec("data"))
            metrics.count("route.device.sharded_chunks")
        if n_blocks > 1:
            metrics.count("route.device.relax_blocks", n_blocks)
        cap = self._iter_cap()
        for lo in range(0, len(pad), block):
            src_dev = jnp.asarray(pad[lo:lo + block])
            if sharding is not None:
                src_dev = jax.device_put(src_dev, sharding)
            dist, time, iters, converged = route_relax.relax_csr(
                self._e_start, self._e_end, self._e_len, self._e_secs,
                src_dev, jnp.float32(chunk_bound),
                n_nodes=self.n_nodes, max_iters=cap)
            if not bool(converged):
                metrics.count("route.device.nonconverged")
                raise RuntimeError(
                    f"route relax did not converge within {cap} sweeps "
                    f"(bound {float(chunk_bound):.0f} m)")
            self.max_iters_seen = max(self.max_iters_seen, int(iters))
            yield lo, dist, time
        self.max_bound_seen = max(self.max_bound_seen, float(chunk_bound))

    def _kernels_cached(self, srcs: np.ndarray, chunk_bound) -> tuple:
        """Serve (dist_sn, time_sn, node_row) from the node-kernel cache,
        relaxing only the rows whose cached bound does not cover this
        chunk's (in power-of-two blocks: a warming cache relaxes few).
        Rows commit per converged block."""
        import jax.numpy as jnp

        missing = srcs[self._row_bound[srcs] < np.float32(chunk_bound)]
        if len(missing):
            if self._cache_dist is None:
                inf = jnp.full((self.n_nodes, self.n_nodes),
                               jnp.inf, jnp.float32)
                self._cache_dist = inf
                self._cache_time = inf
            block = min(_next_pow2(len(missing)), self._block_sources())
            for lo, dist, time in self._relax_blocks(missing, chunk_bound,
                                                     block):
                part = missing[lo:lo + block]
                rows = jnp.asarray(part)
                self._cache_dist = self._cache_dist.at[rows] \
                    .set(dist[:len(part)])
                self._cache_time = self._cache_time.at[rows] \
                    .set(time[:len(part)])
                self._row_bound[part] = np.float32(chunk_bound)
            metrics.count("route.device.cache_miss_rows", int(len(missing)))
        metrics.count("route.device.cache_hit_rows",
                      int(len(srcs) - len(missing)))
        # cache row i belongs to node i: node_row is the identity on the
        # nodes this chunk needs (all just proven covered), -1 elsewhere
        node_row = np.full(self.n_nodes, -1, dtype=np.int32)
        node_row[srcs] = srcs
        return self._cache_dist, self._cache_time, node_row

    def _run(self, edge, offset, nk, bounds, caps, srcs, chunk_bound,
             btol, tpen):
        """Relax (or cache-serve) + assemble; returns the DEVICE
        (B, T-1, K, K) float32 route array and finite-max scalar,
        dispatched but not synced. Split out so route_matrices shares
        it. Uncached, each relaxation block is assembled as it lands
        and merged into the tensor so far (pair_costs_packed), so no
        (sources x nodes) concatenation is ever built and the programs
        run are the same for one block or many."""
        import jax.numpy as jnp

        from ..ops import route_relax

        if self._cache_ok:
            dist, time, node_row = self._kernels_cached(srcs, chunk_bound)
            blocks = [(0, dist, time)]
        else:
            node_row = np.full(self.n_nodes, -1, dtype=np.int32)
            node_row[srcs] = np.arange(len(srcs), dtype=np.int32)
            blocks = self._relax_blocks(srcs, chunk_bound,
                                        self._block_sources())

        # two packed blobs instead of eight small transfers: on a warm
        # cache the per-chunk device_put overhead IS the dispatch cost
        B, T, K = edge.shape
        ints = jnp.asarray(np.concatenate([
            np.ascontiguousarray(edge, dtype=np.int32).ravel(),
            nk.astype(np.int32, copy=False),
            node_row]))
        f32s = jnp.asarray(np.concatenate([
            np.ascontiguousarray(offset, dtype=np.float32).ravel(),
            bounds.ravel(), caps.ravel(),
            np.array([btol, tpen], dtype=np.float32)]))
        cols = (self._e_start, self._e_end, self._e_len, self._e_v,
                self._head_x, self._head_y)
        route = jnp.full((B, T - 1, K, K), UNREACHABLE, jnp.float32)
        for lo, dist, time in blocks:
            route, max_finite = route_relax.pair_costs_packed(
                ints, f32s, dist, time, np.int32(lo), route, *cols,
                B=B, T=T, K=K, N=self.n_nodes)
        # still device arrays: the caller decides when (and whether on
        # this thread) to pay the sync — fill_prep(defer=True) never does
        return route, max_finite

    @staticmethod
    def _mesh():
        from ..parallel import mesh as pmesh
        m = pmesh.decode_mesh()
        if m is None:
            return None
        data, _seq = pmesh.mesh_axes(m)
        return m if data > 1 else None

    def _dispatch_pool(self):
        """The single-worker executor for warm-cache async dispatch.
        One thread: chunk dispatches stay ordered and the node-kernel
        cache is only ever mutated by the (serial) prep thread."""
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="route-dispatch")
        return self._pool

    # -- standalone matrices (tests / bench parity legs) -------------------
    def route_matrices(self, cands, gc,
                       max_route_distance_factor: float = 5.0,
                       min_bound_m: float = 500.0,
                       backward_tolerance_m: float = 25.0,
                       dt=None, max_route_time_factor: float = 0.0,
                       min_time_bound_s: float = 60.0,
                       turn_penalty_factor: float = 0.0) -> np.ndarray:
        """(T-1, K, K) route tensor for one trace's candidate set — the
        device twin of NativeRuntime.route_matrices / graph.route.
        candidate_route_matrices, for the parity legs."""
        edge = np.asarray(cands.edge_ids, dtype=np.int32)[None]
        offset = np.asarray(cands.offset_m, dtype=np.float32)[None]
        T = edge.shape[1]
        if T < 2:
            return np.zeros((0, edge.shape[2], edge.shape[2]),
                            dtype=np.float32)
        gc = np.asarray(gc, dtype=np.float32).reshape(1, T - 1)
        bounds = np.maximum(
            np.float64(min_bound_m),
            np.float64(max_route_distance_factor)
            * gc.astype(np.float64)).astype(np.float32)
        if dt is not None and max_route_time_factor > 0:
            d64 = np.asarray(dt, dtype=np.float64).reshape(1, T - 1)
            caps = np.where(
                d64 > 0,
                np.maximum(np.float64(min_time_bound_s),
                           np.float64(max_route_time_factor) * d64),
                np.float64(-1.0)).astype(np.float32)
        else:
            caps = np.full((1, T - 1), -1.0, dtype=np.float32)
        nk = np.array([T], dtype=np.int32)
        live = edge[:, :T - 1, :] >= 0
        if not bool(live.any()):
            return np.full((T - 1, edge.shape[2], edge.shape[2]),
                           UNREACHABLE, dtype=np.float32)
        srcs = np.unique(self._end_np[edge[:, :T - 1, :][live]])
        route, _ = self._run(edge, offset, nk, bounds, caps, srcs,
                             np.float32(bounds.max()),
                             float(backward_tolerance_m),
                             float(turn_penalty_factor))
        return np.asarray(route)[0]
