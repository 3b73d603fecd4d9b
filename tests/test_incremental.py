"""Incremental matcher (ISSUE 19): byte parity with the windowed batch
path, fixed-lag semantics, carried-state lifecycle, and snapshot serde.

The contract under test is absolute: every report the incremental path
SERVES is byte-identical to ``match_many`` over the same window; every
window it cannot reproduce byte-for-byte (lag non-convergence, evicted
state, bucket overflow) comes back ``None`` and the caller re-routes it
through the batch path — fallback, never approximation.
"""
import json

import numpy as np
import pytest

from reporter_tpu.core.types import Point
from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.matcher import incremental as inc
from reporter_tpu.streaming.batcher import PointBatcher
from reporter_tpu.synth import build_grid_city, generate_trace


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=12, cols=12, spacing_m=200.0, seed=2,
                           service_road_fraction=0.0, internal_fraction=0.0)


@pytest.fixture
def matcher(city):
    # fresh per test: carried state must not leak across tests
    return SegmentMatcher(net=city)


def ser(obj):
    """Normalise either submit-path result shape (dict from the Python
    writer, MatchRuns from the native writer) to canonical JSON."""
    if isinstance(obj, dict):
        return json.dumps(obj, sort_keys=True)
    from reporter_tpu.matcher.matcher import render_segments_json
    s = render_segments_json(obj.cols, obj.lo, obj.hi, obj.mode)
    return json.dumps(json.loads(s), sort_keys=True)


def make_trace(city, seed, noise=4.0, **kw):
    rng = np.random.default_rng(seed)
    for _ in range(500):
        tr = generate_trace(city, f"veh-{seed}", rng, noise_m=noise, **kw)
        if tr is not None:
            return tr
    raise RuntimeError("could not generate a trace")


def stream_parity(m, pts, uuid, start=6, step=3, trim_every=0):
    """Feed growing (optionally prefix-trimmed) windows through BOTH
    paths; assert byte equality wherever the incremental path served.
    Returns (served, windows)."""
    served = windows = 0
    lo = 0
    for hi in range(start, len(pts) + 1, step):
        req = {"uuid": uuid, "trace": pts[lo:hi]}
        got = m.match_incremental([req])[0]
        windows += 1
        if got is not None:
            served += 1
            assert json.dumps(got, sort_keys=True) \
                == ser(m.match_many([req])[0]), \
                f"parity break for {uuid} window [{lo}:{hi}]"
        if trim_every and (hi // step) % trim_every == 0:
            lo = max(lo, hi - 3 * step)  # shape_used-style prefix trim
    return served, windows


def stop_and_go(pts, rng):
    """Inject a stopped-vehicle jitter cluster mid-trace plus a 3 km
    teleport of the tail (breakage -> RESTART)."""
    k = len(pts) // 2
    base = pts[k]
    stop = [dict(lat=base["lat"] + rng.normal(0, 2e-6),
                 lon=base["lon"] + rng.normal(0, 2e-6),
                 time=base["time"] + 1 + i) for i in range(8)]
    shift = stop[-1]["time"] - base["time"]
    tail = [dict(p, time=p["time"] + shift, lat=p["lat"] + 0.027)
            for p in pts[k + 1:]]
    return pts[:k + 1] + stop + tail


class TestParity:
    def test_incremental_matches_batch_noise_profiles(self, city, matcher):
        """The FB registry's parity pin: urban canyon (heavy noise),
        sparse rural (thinned fixes), stop-and-go (jitter clusters +
        breakage teleport) — every served report byte-equals batch."""
        rng = np.random.default_rng(7)
        total_served = 0
        for s in range(2):  # urban canyon: 20 m multipath-grade noise
            pts = list(make_trace(city, seed=100 + s, noise=20.0).points)
            total_served += stream_parity(matcher, pts,
                                          f"canyon-{s}")[0]
        for s in range(2):  # sparse rural: keep every 3rd fix
            pts = list(make_trace(city, seed=200 + s, noise=5.0).points)
            total_served += stream_parity(matcher, pts[::3],
                                          f"rural-{s}", start=4, step=2)[0]
        for s in range(2):  # stop-and-go + breakage
            pts = stop_and_go(
                list(make_trace(city, seed=300 + s, noise=8.0).points), rng)
            total_served += stream_parity(matcher, pts, f"sg-{s}")[0]
        assert total_served > 20  # the path must actually serve, not
        # just fall back its way to vacuous parity

    def test_parity_with_prefix_trims(self, city, matcher):
        """The batcher trims the consumed prefix after a report
        (shape_used): the carried state sees its window shrink from the
        left, resets, replays — and stays byte-exact throughout."""
        pts = list(make_trace(city, seed=42, noise=6.0).points)
        served, _ = stream_parity(matcher, pts, "trim-0", trim_every=2)
        assert served > 0
        assert matcher.incremental_table.resets > 0


class TestBatchedAdvance:
    """A report's appended points are prepped together and advanced
    together: one vector selection, one route call, and one device
    dispatch per ``ROUNDS_MAX`` rounds of a flush's traces."""

    def test_rounds_equal_single_steps(self):
        """R rounds in one call are bit-identical to R one-round calls;
        a trace not live in a round keeps its scores there."""
        from reporter_tpu.ops import incremental_step_batch
        rng = np.random.default_rng(3)
        R, N, K = 5, 3, 8
        dist = rng.uniform(0, 40, (R, N, K)).astype(np.float32)
        valid = rng.random((R, N, K)) < 0.8
        route = rng.uniform(0, 400, (R, N, K, K)).astype(np.float32)
        route[rng.random((R, N, K, K)) < 0.2] = inc.UNREACHABLE
        gc = rng.uniform(0, 300, (R, N)).astype(np.float32)
        case = np.where(rng.random((R, N)) < 0.2, inc.RESTART,
                        inc.NORMAL).astype(np.int32)
        live = np.ones((R, N), dtype=bool)
        live[3:, 1] = False                  # a ragged trace
        prev = rng.uniform(-50, 0, (N, K)).astype(np.float32)
        sigma, beta = np.float32(4.07), np.float32(3.0)
        scores, bp, anchor = (np.asarray(x) for x in incremental_step_batch(
            dist, valid, route, gc, case, live, prev, sigma, beta))
        carry = prev
        for r in range(R):
            one = [np.asarray(x) for x in incremental_step_batch(
                dist[r:r + 1], valid[r:r + 1], route[r:r + 1], gc[r:r + 1],
                case[r:r + 1], live[r:r + 1], carry, sigma, beta)]
            np.testing.assert_array_equal(one[1][0][live[r]], bp[r][live[r]])
            np.testing.assert_array_equal(one[2][0][live[r]],
                                          anchor[r][live[r]])
            carry = one[0]
            if r == 2:
                held = carry[1].copy()
        np.testing.assert_array_equal(carry, scores)
        np.testing.assert_array_equal(scores[1], held)
        assert scores.dtype == np.float32

    @pytest.mark.parametrize("cuts", [(), (1,), (5, 6, 30), (17, 40)])
    def test_selection_matches_batchpad(self, cuts):
        """Kept points selected report by report, from the carried
        anchor, are the ones the batch path selects over the window."""
        from reporter_tpu.matcher.batchpad import _select_kept
        rng = np.random.default_rng(11)
        n = 64
        steps = rng.choice([0.5, 3.0, 12.0], size=n, p=[0.3, 0.3, 0.4])
        lat = 40.0 + np.cumsum(steps) * 9e-6
        lon = -75.0 + rng.normal(0, 2e-6, n)
        has = rng.random(n) < 0.9
        want = _select_kept(lat, lon, has, 5.0).tolist()
        got, anchor = [], None
        for lo, hi in zip((0, *cuts), (*cuts, n)):
            kept = inc._select_appended(
                lat, lon, lo + np.flatnonzero(has[lo:hi]), anchor, 5.0)
            got += kept.tolist()
            if got:
                anchor = (float(lat[got[-1]]), float(lon[got[-1]]))
        assert got == want

    def test_long_append_spans_dispatches(self, city, matcher,
                                          monkeypatch):
        """Reports that append more points than one dispatch's rounds
        advance over several dispatches, byte-equal to the batch path."""
        import reporter_tpu.ops as ops
        calls = []
        real = ops.incremental_step_batch

        def counting(*a):
            calls.append(np.shape(a[0]))
            return real(*a)

        monkeypatch.setattr(ops, "incremental_step_batch", counting)
        monkeypatch.setattr(inc, "ROUNDS_MAX", 4)
        pts = list(make_trace(city, seed=77, noise=4.0).points)
        served, windows = stream_parity(matcher, pts, "long-0", start=20,
                                        step=9)
        assert served == windows > 1
        assert max(r for r, _n, _k in calls) == 4
        assert len(calls) > windows


class TestFixedLag:
    def test_report_inside_lag_window(self, city, matcher, monkeypatch):
        """A report whose whole window fits inside the lag bound decodes
        purely from the uncommitted ring (zero commits) — and still
        byte-matches the batch path."""
        monkeypatch.setenv(inc.ENV_LAG, "64")
        pts = list(make_trace(city, seed=9, noise=4.0).points)[:12]
        served, windows = stream_parity(matcher, pts, "short-0")
        assert served == windows  # nothing to fall back on: no
        # truncation, no f16 hazard, and commits are never forced
        gauge = matcher.incremental_table.gauge()
        assert gauge["traces"] == 1 and gauge["state_bytes"] > 0

    def test_tight_lag_falls_back_not_wrong(self, city, matcher,
                                            monkeypatch):
        """lag=2 (the floor) forces commits long before backtraces can
        converge under noise: fallbacks are expected and fine — but any
        window that IS served must still be byte-exact."""
        monkeypatch.setenv(inc.ENV_LAG, "2")
        pts = list(make_trace(city, seed=17, noise=12.0).points)
        stream_parity(matcher, pts, "tight-0")


class TestLifecycle:
    def test_kill_switch_serves_nothing(self, city, matcher, monkeypatch):
        monkeypatch.setenv(inc.ENV_INCREMENTAL, "off")
        pts = list(make_trace(city, seed=5).points)
        out = matcher.match_incremental([{"uuid": "k", "trace": pts}])
        assert out == [None]

    def test_pressure_shed_clears_state(self, city, matcher):
        pts = list(make_trace(city, seed=6).points)
        assert matcher.match_incremental(
            [{"uuid": "p", "trace": pts}])[0] is not None
        assert matcher.incremental_table.gauge()["traces"] == 1
        try:
            inc.set_pressure_shed(True)
            out = matcher.match_incremental([{"uuid": "p", "trace": pts}])
            assert out == [None]
            assert matcher.incremental_table.gauge()["traces"] == 0
        finally:
            inc.set_pressure_shed(False)

    def test_eviction_falls_back_byte_identically(self, city, matcher):
        """Mid-stream eviction (budget pressure stand-in): the next
        window replays from scratch and parity holds — eviction costs
        work, never bytes."""
        pts = list(make_trace(city, seed=23, noise=6.0).points)
        mid = max(8, len(pts) // 2)
        assert stream_parity(matcher, pts[:mid], "ev-0")[0] > 0
        matcher.incremental_table.evict("ev-0", "test eviction")
        assert matcher.incremental_table.gauge()["traces"] == 0
        served, _ = stream_parity(matcher, pts, "ev-0",
                                  start=mid, step=3)
        assert served > 0

    def test_session_gap_eviction_drops_carried_state(self, city, matcher):
        """The batcher's session-gap eviction (punctuate) rides the
        on_evict hook: the uuid's carried decode state dies WITH the
        session, after its final relaxed-threshold report."""
        pts = list(make_trace(city, seed=31).points)
        assert matcher.match_incremental(
            [{"uuid": "veh", "trace": pts}])[0] is not None
        assert matcher.incremental_table.gauge()["traces"] == 1
        evicted = []

        def on_evict(uuid):
            matcher.incremental_table.evict(uuid, "session gap")
            evicted.append(uuid)

        pb = PointBatcher(lambda t: None, lambda k, s: None,
                          on_evict=on_evict)
        pb.process("veh", Point(14.6, 121.0, 10, 0), stream_time_ms=0)
        pb.punctuate(stream_time_ms=200_000)  # past the 60 s gap
        assert evicted == ["veh"]
        assert matcher.incremental_table.gauge()["traces"] == 0


class TestSerde:
    def test_carried_state_roundtrip_resumes_byte_exact(self, city,
                                                        matcher):
        """to_blobs -> restore_blobs into a FRESH matcher resumes the
        decode mid-stream with parity intact (the crash-restore path,
        snapshot v3)."""
        pts = list(make_trace(city, seed=55, noise=6.0).points)
        mid = max(9, (len(pts) // 2) // 3 * 3)
        assert stream_parity(matcher, pts[:mid], "crash-0")[0] > 0
        blobs = matcher.incremental_table.to_blobs()
        assert blobs and all(isinstance(b, bytes) for _, b in blobs)

        m2 = SegmentMatcher(net=city)
        assert m2.incremental_table.restore_blobs(blobs) == len(blobs)
        # resumed table picks up where the dead worker stopped: the
        # appended points advance the RESTORED state (resets stay 0)
        served, _ = stream_parity(m2, pts, "crash-0", start=mid, step=3)
        assert served > 0
        assert m2.incremental_table.resets == 0

    def test_blob_roundtrip_carries_map_version(self, city, matcher):
        """Carried state is keyed to the map build that produced it
        (ISSUE 20): the v2 blob trailer round-trips the graph's
        content-derived version."""
        pts = list(make_trace(city, seed=77, noise=5.0).points)
        stream_parity(matcher, pts, "epoch-0")
        table = matcher.incremental_table
        assert table.map_version
        assert table.gauge()["map_version"] == table.map_version
        blobs = table.to_blobs()
        assert blobs
        st = inc.CarriedState.from_bytes(blobs[0][1])
        assert st.map_version == table.map_version
        # an unversioned state (ver-1 era) round-trips None
        bare = inc.CarriedState((1.0, 2.0), False, 4, map_version=None)
        assert inc.CarriedState.from_bytes(
            bare.to_bytes()).map_version is None

    def test_swap_resets_carried_state_against_new_graph(self, city,
                                                         matcher):
        """A hot map swap invalidates carried decode state: a restored
        state from vN RESETS on the vN+1 table (batch-oracle re-frame)
        instead of advancing a decode against the wrong graph, and the
        replayed window still holds byte parity on the new graph."""
        pts = list(make_trace(city, seed=78, noise=5.0).points)
        mid = max(9, (len(pts) // 2) // 3 * 3)
        stream_parity(matcher, pts[:mid], "swap-0")
        blobs = matcher.incremental_table.to_blobs()
        assert blobs

        city2 = build_grid_city(rows=12, cols=12, spacing_m=200.0,
                                seed=2, service_road_fraction=0.0,
                                internal_fraction=0.0)
        city2.edge_speed_kph = city2.edge_speed_kph * 1.3
        m2 = SegmentMatcher(net=city2)
        t2 = m2.incremental_table
        assert t2.map_version != matcher.incremental_table.map_version
        # the blobs parse fine (work avoidance is graph-agnostic)...
        assert t2.restore_blobs(blobs) == len(blobs)
        r0 = t2.resets
        # ...but the first report on the new graph drops them
        stream_parity(m2, pts, "swap-0", start=mid, step=3)
        assert t2.resets > r0

    def test_corrupt_blob_is_skipped_not_fatal(self, city, matcher):
        n = matcher.incremental_table.restore_blobs(
            [("bad", b"\x00\x01garbage")])
        assert n == 0
        assert matcher.incremental_table.gauge()["traces"] == 0

    def test_state_snapshot_v3_carries_frames(self, city, matcher,
                                              tmp_path):
        """StateStore.save tees the carried state into the v3 snapshot;
        restore hands it back through the provider."""
        from reporter_tpu.streaming.anonymiser import Anonymiser
        from reporter_tpu.streaming.state import StateStore

        class NullSink:
            def write(self, *a, **k):
                return None

        pts = list(make_trace(city, seed=71).points)
        assert matcher.match_incremental(
            [{"uuid": "snap", "trace": pts}])[0] is not None

        path = str(tmp_path / "state.bin")
        store = StateStore(path, incremental=lambda:
                           matcher.incremental_table)
        pb = PointBatcher(lambda t: None, lambda k, s: None)
        anon = Anonymiser(NullSink(), 2, 60)
        store.save(pb, anon)

        m2 = SegmentMatcher(net=city)
        store2 = StateStore(path, incremental=lambda:
                            m2.incremental_table)
        pb2 = PointBatcher(lambda t: None, lambda k, s: None)
        anon2 = Anonymiser(NullSink(), 2, 60)
        assert store2.restore(pb2, anon2)
        assert m2.incremental_table.gauge()["traces"] == 1

    def test_v2_snapshot_still_restores(self, city, tmp_path):
        """A pre-incremental (v2) snapshot restores batches/slices as
        before — the missing section is an empty cache, not corruption."""
        from reporter_tpu.streaming import state as state_mod
        from reporter_tpu.streaming.anonymiser import Anonymiser
        from reporter_tpu.streaming.state import StateStore

        class NullSink:
            def write(self, *a, **k):
                return None

        pb = PointBatcher(lambda t: None, lambda k, s: None)
        pb.process("veh", Point(14.6, 121.0, 10, 0), stream_time_ms=0)
        anon = Anonymiser(NullSink(), 2, 60)
        raw = bytearray(state_mod.snapshot_bytes(pb, anon))
        # rewrite the header version to 2 and drop the (empty)
        # incremental section's count field
        import struct
        struct.pack_into("<I", raw, 4, 2)
        raw = bytes(raw[:-4])

        path = str(tmp_path / "state.bin")
        with open(path, "wb") as f:
            f.write(raw)
        pb2 = PointBatcher(lambda t: None, lambda k, s: None)
        anon2 = Anonymiser(NullSink(), 2, 60)
        assert StateStore(path).restore(pb2, anon2)
        assert "veh" in pb2.store
