"""ISSUE 16: device-resident batched route costs.

The route-cost stage has three implementations that must agree to the
byte: the chunk-batched device relax (ops/route_relax.py +
graph/route_device.py), the numpy host Dijkstra
(graph.route.candidate_route_matrices) and the native memo
(rt_route_matrices / rt_prepare_batch's route_step). These tests pin:

- edge-semantics parity on crafted candidate sets — unroutable
  (bound-exceeded) pairs, zero-length same-edge pairs, backward jitter
  within/without tolerance, time-capped transitions, padding — identical
  verdicts AND identical bytes across all three paths;
- chunk-level parity through ``prepare_batch(route_kernel=...)``,
  including pow2/mesh filler rows and the dead trailing step;
- report bytes identical with the device kernel on vs off
  (REPORTER_TPU_ROUTE_DEVICE), the acceptance contract;
- the ABI-14 native additions: the ``dt`` output tensor and
  ``skip_routes``;
- FLASH-style candidate pruning (REPORTER_TPU_ROUTE_PRUNE_SIGMA):
  C++ prune == numpy prune, the best candidate always survives, and a
  malformed spec degrades to pruning off;
- the ``route.device`` circuit domain: forced non-convergence
  (REPORTER_TPU_ROUTE_HOPS=1) falls back to host routes byte-identically.
"""
import numpy as np
import pytest

from reporter_tpu import native
from reporter_tpu.graph.route import UNREACHABLE, candidate_route_matrices
from reporter_tpu.graph.spatial import PAD_EDGE, CandidateSet
from reporter_tpu.matcher import MatchParams, SegmentMatcher
from reporter_tpu.matcher.batchpad import bucket_length, prepare_batch
from reporter_tpu.synth import build_grid_city, generate_trace
from reporter_tpu.utils import metrics

jax = pytest.importorskip("jax")

UNREACH = np.float32(UNREACHABLE)
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=8, cols=8, spacing_m=200.0, seed=3)


@pytest.fixture(scope="module")
def kernel(city):
    from reporter_tpu.graph.route_device import DeviceRouteKernel
    return DeviceRouteKernel(city)


def _reqs(city, n=6, seed=11, max_pts=48):
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        tr = generate_trace(city, f"rd-{len(out)}", rng, noise_m=4.0,
                            min_route_edges=4, max_route_edges=20)
        if tr is None or len(tr.points) < 4:
            continue
        tr.points = tr.points[:max_pts]
        out.append({"uuid": tr.uuid, "trace": tr.points,
                    "match_options": {"mode": "auto",
                                      "report_levels": [0, 1, 2],
                                      "transition_levels": [0, 1, 2]}})
    return out


def _report_bytes(m, reqs):
    from reporter_tpu.service.report import report_json
    return [report_json(match, req, 15, {0, 1, 2}, {0, 1, 2})
            for match, req in zip(m.match_many(reqs), reqs)]


def _pick_edges(city):
    """(e0, e1, e_far): an edge, a continuation out of its end node that
    is not its reverse, and an edge starting far (> the 500 m floor)
    from e0's end node."""
    e_start = np.asarray(city.edge_start)
    e_end = np.asarray(city.edge_end)
    e_len = np.asarray(city.edge_length_m)
    e0 = int(np.argmax(e_len >= 60.0))
    nxt = np.flatnonzero(e_start == e_end[e0])
    e1 = int(nxt[0] if e_end[nxt[0]] != e_start[e0] else nxt[-1])
    lat = np.asarray(city.node_lat)
    lon = np.asarray(city.node_lon)
    d2 = (lat[e_start] - lat[e_end[e0]]) ** 2 \
        + (lon[e_start] - lon[e_end[e0]]) ** 2
    e_far = int(np.argmax(d2))
    return e0, e1, e_far


def _crafted(city):
    """A hand-built (T=4, K=2) candidate set covering the emit ladder:
    backward-within-tolerance, same-edge forward, an adjacent routable
    pair, bound-exceeded (unroutable) far pairs, and a padding slot."""
    e0, e1, e_far = _pick_edges(city)
    edge = np.array([[e0, e0],
                     [e0, e1],
                     [e_far, PAD_EDGE],
                     [e0, e1]], dtype=np.int32)
    offset = np.array([[50.0, 10.0],
                       [30.0, 30.0],
                       [5.0, 0.0],
                       [20.0, 40.0]], dtype=np.float32)
    z = np.zeros_like(offset)
    cands = CandidateSet(edge_ids=edge, dist_m=z + 1.0, offset_m=offset,
                         proj_x=z, proj_y=z)
    # small gc -> every per-step bound is the 500 m floor
    gc = np.array([30.0, 40.0, 30.0], dtype=np.float32)
    return cands, gc


class TestEdgeSemantics:
    """Crafted pairs: identical verdicts and bytes across all paths."""

    def _all_paths(self, city, kernel, cands, gc, **kw):
        # the three implementations default backward_tolerance_m and
        # min_time_bound_s differently (serving passes params
        # explicitly); pin them so the parity compares like with like
        kw.setdefault("backward_tolerance_m", 25.0)
        dev = kernel.route_matrices(cands, gc, **kw)
        host = candidate_route_matrices(city, cands, gc, **kw)
        outs = [dev, host]
        if native.available():
            rt = native.NativeRuntime(city)
            outs.append(rt.route_matrices(cands, gc, **kw))
        for other in outs[1:]:
            assert np.array_equal(outs[0], other)
        return dev

    def test_distance_semantics(self, city, kernel):
        cands, gc = _crafted(city)
        e0 = int(cands.edge_ids[0, 0])
        route = self._all_paths(city, kernel, cands, gc)
        # (e0,50)->(e0,30): 20 m backward <= 25 m tolerance -> free
        assert route[0, 0, 0] == 0.0
        # (e0,10)->(e0,30): same-edge forward -> exactly the 20 m delta
        assert route[0, 1, 0] == np.float32(20.0)
        # (e0,50)->(e1,30): continuation out of e0's end node -> the
        # f32 path-order sum remaining + ob (+ 0 network meters)
        e_len0 = np.float32(np.asarray(city.edge_length_m)[e0])
        want = np.float32(np.float32(e_len0 - np.float32(50.0))
                          + np.float32(30.0))
        assert route[0, 0, 1] == want
        # t1 -> t2: e_far starts > 500 m (the bound floor) away ->
        # unroutable; the padding candidate column is unreachable too
        assert (route[1] == UNREACH).all()
        # t2 -> t3: from e_far (far pair) and from the pad slot
        assert (route[2] == UNREACH).all()

    def test_backward_beyond_tolerance_prices_as_loop(self, city, kernel):
        """40 m backward on one edge exceeds the 25 m tolerance: the
        pair prices as the general loop path — finite (the grid has a
        reverse edge) but never the free backward case."""
        e0, _e1, _f = _pick_edges(city)
        edge = np.array([[e0], [e0]], dtype=np.int32)
        off = np.array([[50.0], [10.0]], dtype=np.float32)
        z = np.zeros_like(off)
        cands = CandidateSet(edge, z + 1.0, off, z, z)
        gc = np.array([30.0], dtype=np.float32)
        route = self._all_paths(city, kernel, cands, gc)
        assert 0.0 < route[0, 0, 0] < UNREACH

    def test_zero_length_same_edge_pair(self, city, kernel):
        cands, gc = _crafted(city)
        off = cands.offset_m.copy()
        off[1, 0] = off[0, 0]  # (e0,50)->(e0,50): zero forward progress
        cands = CandidateSet(cands.edge_ids, cands.dist_m, off,
                             cands.proj_x, cands.proj_y)
        route = self._all_paths(city, kernel, cands, gc)
        assert route[0, 0, 0] == 0.0

    def test_time_cap_semantics(self, city, kernel):
        """A 0.1 s probe delta with a 1 s floor caps every cross-edge
        transition (hundreds of meters at street speed) while the
        zero-length same-edge pair stays free — on all three paths."""
        cands, gc = _crafted(city)
        off = cands.offset_m.copy()
        off[1, 0] = off[0, 0]
        cands = CandidateSet(cands.edge_ids, cands.dist_m, off,
                             cands.proj_x, cands.proj_y)
        dt = np.array([0.1, 0.1, 0.1], dtype=np.float64)
        route = self._all_paths(city, kernel, cands, gc, dt=dt,
                                max_route_time_factor=2.0,
                                min_time_bound_s=1.0)
        assert route[0, 0, 0] == 0.0          # zero meters, zero seconds
        assert route[0, 0, 1] == UNREACH      # adjacent hop, capped

    def test_unroutable_everything_padded(self, city, kernel):
        """An all-pad candidate set returns the all-UNREACHABLE tensor
        (the tail-fill bytes) from the device path too."""
        edge = np.full((3, 2), PAD_EDGE, dtype=np.int32)
        z = np.zeros((3, 2), dtype=np.float32)
        cands = CandidateSet(edge, z, z, z, z)
        gc = np.zeros(2, dtype=np.float32)
        route = kernel.route_matrices(cands, gc)
        assert route.shape == (2, 2, 2)
        assert (route == UNREACH).all()


@needs_native
class TestChunkParity:
    """prepare_batch: device-filled chunks byte-identical to host."""

    @pytest.fixture(scope="class")
    def matcher(self, city):
        return SegmentMatcher(net=city,
                              params=MatchParams(max_candidates=8))

    def _chunks(self, matcher, kernel, reqs, **pb_kw):
        pts = [r["trace"] for r in reqs]
        T = max(bucket_length(len(p)) for p in pts)
        host = prepare_batch(matcher.runtime, pts, matcher.params, T,
                             **pb_kw)
        dev = prepare_batch(matcher.runtime, pts, matcher.params, T,
                            route_kernel=kernel, **pb_kw)
        return host, dev

    def test_route_tensor_byte_identical(self, city, matcher, kernel):
        host, dev = self._chunks(matcher, kernel, _reqs(city))
        assert host.prep["route_m"].tobytes() \
            == dev.prep["route_m"].tobytes()
        assert np.asarray(host.route_m).tobytes() \
            == np.asarray(dev.route_m).tobytes()  # post wire-cast too
        for k in ("edge_ids", "dist_m", "offset_m", "gc_m", "case",
                  "kept_idx", "num_kept", "dt", "max_finite"):
            assert np.array_equal(host.prep[k], dev.prep[k]), k

    def test_deferred_routes_finalize_matches_sync(self, city, matcher,
                                                   kernel):
        """prepare_batch(defer_routes=True) ships the in-flight device
        tensor + a finalize closure; after finalize_wire the batch
        tensors and the prep dict are byte-identical to the synchronous
        device path (wire cast included)."""
        pts = [r["trace"] for r in _reqs(city)]
        T = max(bucket_length(len(p)) for p in pts)
        sync = prepare_batch(matcher.runtime, pts, matcher.params, T,
                             route_kernel=kernel)
        metrics.default.reset()
        deferred = prepare_batch(matcher.runtime, pts, matcher.params, T,
                                 route_kernel=kernel, defer_routes=True)
        # the sync call above warmed the node-kernel cache, so this
        # deferred chunk must have taken the fully-async dispatch path
        snap = metrics.default.snapshot()["counters"]
        assert snap.get("route.device.async_dispatch_chunks", 0) == 1
        assert deferred.finalize is not None
        assert deferred.route_m is None  # installed by finalize_wire
        deferred.finalize_wire()
        assert deferred.finalize is None
        assert np.asarray(deferred.route_m, dtype=np.float32).tobytes() \
            == np.asarray(sync.route_m, dtype=np.float32).tobytes()
        assert np.asarray(deferred.route_m).dtype \
            == np.asarray(sync.route_m).dtype  # same wire decision
        assert deferred.prep["route_m"].tobytes() \
            == sync.prep["route_m"].tobytes()
        assert np.array_equal(deferred.prep["max_finite"],
                              sync.prep["max_finite"])
        deferred.finalize_wire()  # idempotent no-op

    def test_filler_rows_skip_cleanly(self, city, matcher, kernel):
        """pow2/mesh filler rows: 5 traces padded to 8 rows — the
        device path must leave rows 5..8 exactly as the native prefill
        wrote them (all-UNREACHABLE), and the real rows byte-equal."""
        host, dev = self._chunks(matcher, kernel, _reqs(city, n=5),
                                 pad_rows=8)
        hr, dr = host.prep["route_m"], dev.prep["route_m"]
        assert hr.shape[0] == 8
        assert hr.tobytes() == dr.tobytes()
        assert (dr[5:] == UNREACH).all()

    def test_programs_follow_rows_not_traces(self, city, matcher,
                                             monkeypatch):
        """Uncached, in blocks: chunks of 5 and 7 traces padded to 8
        rows, with different source counts, run the same compiled
        relaxation and assembly (the metro serving path must not
        compile per chunk) and stay byte-identical to host routes."""
        from reporter_tpu.graph import route_device
        from reporter_tpu.ops import route_relax

        monkeypatch.setattr(route_device, "_STATE_BUDGET_ELEMS",
                            8 * max(city.num_nodes, city.num_edges))
        kern = route_device.DeviceRouteKernel(city)
        kern._cache_ok = False
        assert kern._block_sources() == 4
        reqs = _reqs(city, n=7)
        T = max(bucket_length(len(r["trace"])) for r in reqs)
        programs, sources = [], []
        for n in (5, 7):
            pts = [r["trace"] for r in reqs[:n]]
            host = prepare_batch(matcher.runtime, pts, matcher.params, T,
                                 pad_rows=8)
            metrics.default.reset()
            dev = prepare_batch(matcher.runtime, pts, matcher.params, T,
                                pad_rows=8, route_kernel=kern,
                                defer_routes=True)
            dev.finalize_wire()
            assert host.prep["route_m"].tobytes() \
                == dev.prep["route_m"].tobytes()
            assert np.asarray(host.route_m).tobytes() \
                == np.asarray(dev.route_m).tobytes()
            sources.append(metrics.default.counter("route.device.sources"))
            programs.append((route_relax.relax_csr._cache_size(),
                             route_relax.pair_costs_packed._cache_size()))
        assert sources[0] != sources[1] and min(sources) > 4
        assert programs[0] == programs[1]

    def test_single_point_and_short_traces(self, city, matcher, kernel):
        """nk<=1 traces have no live transitions; mixed with real
        traces the device fill must reproduce the tail-fill bytes."""
        reqs = _reqs(city, n=3)
        reqs[1] = dict(reqs[1], trace=reqs[1]["trace"][:1])
        host, dev = self._chunks(matcher, kernel, reqs)
        assert host.prep["route_m"].tobytes() \
            == dev.prep["route_m"].tobytes()

    def test_dt_tensor_contract(self, city, matcher, kernel):
        """ABI 14: ``dt`` carries kept-point probe time deltas for
        t < num_kept-1 and the -1 sentinel everywhere else (including
        filler rows)."""
        host, _ = self._chunks(matcher, kernel, _reqs(city, n=3),
                               pad_rows=4)
        prep, dt = host.prep, host.prep["dt"]
        for b in range(3):
            nk = int(prep["num_kept"][b])
            view = host.traces[b]
            kept_times = view.times[prep["kept_idx"][b, :nk]]
            if nk > 1:
                assert np.array_equal(dt[b, :nk - 1],
                                      np.diff(kept_times))
            assert (dt[b, max(nk - 1, 0):] == -1.0).all()
        assert (dt[3:] == -1.0).all()

    def test_skip_routes_leaves_tail_fill(self, city, matcher, kernel):
        """skip_routes skips ONLY route_step: candidates/case/dt match a
        full prep, and route rows at/after num_kept-1 still carry the
        tail fill the device path relies on."""
        pts = [r["trace"] for r in _reqs(city, n=2)]
        T = max(bucket_length(len(p)) for p in pts)
        params = matcher.params
        pt_off = np.zeros(len(pts) + 1, dtype=np.int64)
        np.cumsum([len(p) for p in pts], out=pt_off[1:])
        lat = np.array([p["lat"] for ps in pts for p in ps])
        lon = np.array([p["lon"] for ps in pts for p in ps])
        times = np.array([p["time"] for ps in pts for p in ps])

        def prep(skip):
            return matcher.runtime.prepare_batch(
                pt_off, lat, lon, times, T, params.max_candidates,
                search_radius=params.search_radius,
                interpolation_distance=params.interpolation_distance,
                breakage_distance=params.breakage_distance,
                max_route_time_factor=params.max_route_time_factor,
                min_time_bound_s=params.min_time_bound_s,
                skip_routes=skip)

        full, skip = prep(False), prep(True)
        for k in ("edge_ids", "dist_m", "offset_m", "gc_m", "case",
                  "kept_idx", "num_kept", "dt"):
            assert np.array_equal(full[k], skip[k]), k
        for b in range(2):
            n = int(full["num_kept"][b])
            assert np.array_equal(full["route_m"][b, max(n - 1, 0):],
                                  skip["route_m"][b, max(n - 1, 0):])


@needs_native
class TestReportBytes:
    """The acceptance contract: REPORTER_TPU_ROUTE_DEVICE on/off emits
    byte-identical report bodies."""

    def test_reports_byte_identical(self, city, monkeypatch):
        reqs = _reqs(city, n=5)  # non-pow2: filler rows in play
        want = _report_bytes(SegmentMatcher(net=city), reqs)
        monkeypatch.setenv("REPORTER_TPU_ROUTE_DEVICE", "1")
        m = SegmentMatcher(net=city)
        metrics.default.reset()
        got = _report_bytes(m, reqs)
        assert got == want
        snap = metrics.default.snapshot()["counters"]
        assert snap.get("route.device.chunks", 0) > 0
        assert m.circuit_route.snapshot()["state"] == "closed"

    def test_forced_nonconvergence_falls_back(self, city, monkeypatch):
        """REPORTER_TPU_ROUTE_HOPS=1 starves the relax; the chunk must
        re-prep through host routes byte-identically and count the
        failure on the route.device circuit."""
        reqs = _reqs(city, n=4)
        want = _report_bytes(SegmentMatcher(net=city), reqs)
        monkeypatch.setenv("REPORTER_TPU_ROUTE_DEVICE", "1")
        monkeypatch.setenv("REPORTER_TPU_ROUTE_HOPS", "1")
        m = SegmentMatcher(net=city)
        metrics.default.reset()
        got = _report_bytes(m, reqs)
        assert got == want
        snap = metrics.default.snapshot()["counters"]
        assert snap.get("route.device.nonconverged", 0) > 0
        assert snap.get("route.device.fallback_chunks", 0) > 0

    def test_route_domain_registered(self):
        assert ("route.device", "circuit_route") \
            in SegmentMatcher.CIRCUIT_DOMAINS


@needs_native
class TestPruning:
    """FLASH-style candidate pruning: C++ == numpy, best candidate
    survives, prune is a sorted-suffix cut, malformed spec = off."""

    def test_native_prune_matches_numpy_prune(self, city, monkeypatch):
        """The batched C++ prune (rt_prepare_batch) and the per-trace
        numpy mirror (batchpad._prune_candidates) pick the same
        survivors and produce the same tensors."""
        monkeypatch.setenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA", "1.5")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=8))
        pts = [r["trace"] for r in _reqs(city, n=4)]
        T = max(bucket_length(len(p)) for p in pts)
        batch = prepare_batch(m.runtime, pts, m.params, T)
        for b, p in enumerate(pts):
            old, new = m.prepare(p), batch.traces[b]
            assert old.num_kept == new.num_kept
            nk = old.num_kept
            np.testing.assert_array_equal(old.edge_ids[:nk],
                                          new.edge_ids[:nk])
            np.testing.assert_allclose(old.dist_m[:nk], new.dist_m[:nk],
                                       rtol=1e-6, atol=1e-4)
            if nk > 1:
                np.testing.assert_allclose(old.route_m[:nk - 1],
                                           new.route_m[:nk - 1],
                                           rtol=1e-5, atol=1e-3)

    def test_prune_is_suffix_and_keeps_best(self, city, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA", "0.5")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=8))
        pts = [r["trace"] for r in _reqs(city, n=4)]
        T = max(bucket_length(len(p)) for p in pts)
        pruned = prepare_batch(m.runtime, pts, m.params, T).prep
        monkeypatch.delenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA")
        full = prepare_batch(m.runtime, pts, m.params, T).prep
        margin = np.float32(0.5 * m.params.effective_sigma)
        cut_any = False
        for b in range(len(pts)):
            for t in range(int(full["num_kept"][b])):
                fe = full["edge_ids"][b, t]
                pe = pruned["edge_ids"][b, t]
                live = np.flatnonzero(pe != PAD_EDGE)
                # live slots are a prefix, and slot 0 always survives
                assert live.size >= 1 and live[-1] == live.size - 1
                assert pe[0] == fe[0]
                # survivors are exactly the within-margin prefix
                # (distance-sorted, f32 compare like the numpy mirror)
                fd = full["dist_m"][b, t]
                keep = ~(fd > fd[0] + margin) & (fe != PAD_EDGE)
                assert np.array_equal(pe != PAD_EDGE, keep)
                cut_any |= int(keep.sum()) < int((fe != PAD_EDGE).sum())
        assert cut_any  # the margin actually bit on this workload

    def test_pruned_reports_identical_across_route_paths(self, city,
                                                         monkeypatch):
        """Pruning shrinks K for BOTH route paths identically, so the
        on/off report parity must hold under it too."""
        monkeypatch.setenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA", "1.0")
        reqs = _reqs(city, n=4)
        want = _report_bytes(SegmentMatcher(net=city), reqs)
        monkeypatch.setenv("REPORTER_TPU_ROUTE_DEVICE", "1")
        got = _report_bytes(SegmentMatcher(net=city), reqs)
        assert got == want

    def test_malformed_spec_disables_pruning(self, city, monkeypatch,
                                             caplog):
        import logging
        monkeypatch.setenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA", "lots")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=8))
        pts = [r["trace"] for r in _reqs(city, n=2)]
        T = max(bucket_length(len(p)) for p in pts)
        with caplog.at_level(logging.WARNING, "reporter_tpu.matcher"):
            got = prepare_batch(m.runtime, pts, m.params, T).prep
        monkeypatch.delenv("REPORTER_TPU_ROUTE_PRUNE_SIGMA")
        want = prepare_batch(m.runtime, pts, m.params, T).prep
        assert np.array_equal(got["edge_ids"], want["edge_ids"])
        assert any("ROUTE_PRUNE_SIGMA" in r.getMessage()
                   for r in caplog.records)


class TestRelaxKernel:
    """ops/route_relax.py unit contracts that parity can't see."""

    def test_relax_exact_vs_reference_dijkstra(self, city, kernel):
        """Relaxed bounded distances equal a reference float32 Dijkstra
        from the same sources (inf where the bound cuts)."""
        import heapq

        import jax.numpy as jnp

        from reporter_tpu.ops import route_relax
        e_start = np.asarray(city.edge_start)
        e_end = np.asarray(city.edge_end)
        e_len = np.asarray(city.edge_length_m, dtype=np.float32)
        n = int(city.num_nodes)
        srcs = np.array([0, n // 2, n - 1], dtype=np.int32)
        bound = np.float32(700.0)
        dist, _t, _i, conv = route_relax.relax_csr(
            kernel._e_start, kernel._e_end, kernel._e_len,
            kernel._e_secs, jnp.asarray(srcs), jnp.float32(bound),
            n_nodes=n, max_iters=n)
        assert bool(conv)
        dist = np.asarray(dist)
        adj = {}
        for e in range(len(e_start)):
            adj.setdefault(int(e_start[e]), []).append(e)
        for row, s in enumerate(srcs):
            ref = np.full(n, np.inf, dtype=np.float32)
            ref[s] = np.float32(0.0)
            heap = [(np.float32(0.0), int(s))]
            while heap:
                d, u = heapq.heappop(heap)
                if d > ref[u]:
                    continue
                for e in adj.get(u, ()):
                    nd = d + e_len[e]  # float32, the kernel's path order
                    if nd > bound:
                        continue
                    v = int(e_end[e])
                    if nd < ref[v]:
                        ref[v] = nd
                        heapq.heappush(heap, (nd, v))
            assert np.array_equal(dist[row], ref)

    def test_nonconvergence_reported(self, city, kernel):
        import jax.numpy as jnp

        from reporter_tpu.ops import route_relax
        _d, _t, _i, conv = route_relax.relax_csr(
            kernel._e_start, kernel._e_end, kernel._e_len,
            kernel._e_secs, jnp.asarray(np.array([0], dtype=np.int32)),
            jnp.float32(1e6), n_nodes=int(city.num_nodes), max_iters=1)
        assert not bool(conv)

    def test_node_kernel_cache_hits_stay_exact(self, city):
        """A warm kernel serves repeat chunks from the node-kernel cache
        (hit rows counted, no second relax of the same sources at the
        same bound) and the routes stay byte-identical to a cold
        kernel's — the monotone-bound reuse rule."""
        from reporter_tpu.graph.route_device import DeviceRouteKernel
        from reporter_tpu.utils import metrics

        cands, gc = _crafted(city)
        warm = DeviceRouteKernel(city)
        assert warm._cache_ok  # the 64-node grid fits the cache budget
        first = warm.route_matrices(cands, gc)
        metrics.default.reset()
        again = warm.route_matrices(cands, gc)
        snap = metrics.default.snapshot()["counters"]
        assert snap.get("route.device.cache_hit_rows", 0) > 0
        assert snap.get("route.device.cache_miss_rows", 0) == 0
        cold = DeviceRouteKernel(city).route_matrices(cands, gc)
        assert np.array_equal(first, again)
        assert np.array_equal(first, cold)

    def test_cache_re_relaxes_on_larger_bound(self, city):
        """A query bound above a row's cached bound must re-relax that
        row (cached rows are exact only DOWN the bound ladder)."""
        from reporter_tpu.graph.route_device import DeviceRouteKernel
        from reporter_tpu.utils import metrics

        cands, gc = _crafted(city)
        kern = DeviceRouteKernel(city)
        kern.route_matrices(cands, gc)  # cached at max(500, 5*gc)
        metrics.default.reset()
        wide = kern.route_matrices(cands, gc, min_bound_m=2000.0)
        snap = metrics.default.snapshot()["counters"]
        assert snap.get("route.device.cache_miss_rows", 0) > 0
        cold = DeviceRouteKernel(city).route_matrices(
            cands, gc, min_bound_m=2000.0)
        assert np.array_equal(wide, cold)

    def test_blocked_relax_is_exact(self, city, monkeypatch):
        """Sources over one block's working-set budget relax in blocks
        (the metro-graph path: ~900 sources per 32-trace chunk on 22,500
        nodes) — routes byte-identical to one whole relaxation."""
        from reporter_tpu.graph import route_device
        from reporter_tpu.utils import metrics

        cands, gc = _crafted(city)
        whole = route_device.DeviceRouteKernel(city)
        whole._cache_ok = False
        want = whole.route_matrices(cands, gc)
        blocked = route_device.DeviceRouteKernel(city)
        blocked._cache_ok = False
        # room for exactly two sources per block
        monkeypatch.setattr(route_device, "_STATE_BUDGET_ELEMS",
                            4 * max(city.num_nodes, city.num_edges))
        assert blocked._block_sources() == 2
        metrics.default.reset()
        got = blocked.route_matrices(cands, gc)
        assert metrics.default.counter("route.device.relax_blocks") > 1
        assert np.array_equal(got, want)

    def test_budget_guard_raises(self, city, kernel, monkeypatch):
        from reporter_tpu.graph import route_device
        monkeypatch.setattr(route_device, "_STATE_BUDGET_ELEMS", 8)
        out = {"edge_ids": np.zeros((1, 3, 1), dtype=np.int32),
               "num_kept": np.array([3], dtype=np.int32),
               "gc_m": np.full((1, 3), 10.0, np.float32),
               "dt": np.full((1, 3), -1.0),
               "offset_m": np.zeros((1, 3, 1), np.float32),
               "route_m": np.zeros((1, 3, 1, 1), np.float32),
               "max_finite": np.zeros(1, np.float32)}
        with pytest.raises(RuntimeError, match="over budget"):
            kernel.fill_prep(out, MatchParams(max_candidates=1))


class TestProfileTable:
    """The .profile frontier-bound table round-trip."""

    def test_stats_seed_roundtrip(self, city, kernel):
        kernel.max_iters_seen = 7
        kernel.max_bound_seen = 900.0
        assert kernel.stats() == {"route_hops": 7,
                                  "route_bound_m": 900.0}
        kernel.seed_hint(7)
        assert kernel._iter_cap() == 16  # 2x hint, floored at 16
        kernel.seed_hint(40)
        assert kernel._iter_cap() == 80

    def test_hops_knob_overrides(self, city, kernel, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_ROUTE_HOPS", "33")
        assert kernel._iter_cap() == 33
        monkeypatch.setenv("REPORTER_TPU_ROUTE_HOPS", "nope")
        assert kernel._iter_cap() >= 2  # malformed -> auto, warned

    @needs_native
    def test_profile_export_carries_route_table(self, city, tmp_path,
                                                monkeypatch):
        from reporter_tpu.datastore import profile as dprofile
        monkeypatch.setenv("REPORTER_TPU_ROUTE_DEVICE", "1")
        m = SegmentMatcher(net=city)
        m.match_many(_reqs(city, n=3))
        path = str(tmp_path / "city.profile")
        art = dprofile.export_profile(m, path, city="test")
        table = art["route_table"]
        assert table is not None and table["route_hops"] > 0
        # warming a fresh matcher seeds its kernel's sweep cap
        m2 = SegmentMatcher(net=city)
        dprofile.warm_matcher(m2, dprofile.load_profile(path))
        kern = m2._device_route_kernel()
        assert kern is not None
        assert kern._hops_hint == table["route_hops"]
