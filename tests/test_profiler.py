"""Device-level profiler (obs/profiler.py): compile telemetry,
bucket-occupancy wide events, shadow-accuracy sampling, and the perf
ledger/gate tools — ISSUE 8."""
import importlib.util
import json
import os
import sys
import types
import urllib.request

import numpy as np
import pytest

from reporter_tpu.matcher import MatchParams, SegmentMatcher
from reporter_tpu.matcher import batchpad
from reporter_tpu.matcher.batchpad import (
    LENGTH_BUCKETS, bucket_length, kept_point_count, occupancy_stats,
    pack_batches, prepare_trace)
from reporter_tpu.obs import profiler
from reporter_tpu.obs import trace as obs_trace
from reporter_tpu.synth import build_grid_city, generate_trace
from reporter_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=10, cols=10, spacing_m=200.0, seed=6,
                           service_road_fraction=0.0,
                           internal_fraction=0.0)


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    yield
    profiler.reset()


def _counter(name):
    return metrics.counter(name)


# ---------------------------------------------------------------------------
class TestBucketLength:
    """batchpad.bucket_length edge semantics (satellite)."""

    def test_exact_boundaries_stay_in_bucket(self):
        for b in LENGTH_BUCKETS:
            assert bucket_length(b) == b

    def test_one_past_a_boundary_moves_up(self):
        for lo, hi in zip(LENGTH_BUCKETS, LENGTH_BUCKETS[1:]):
            assert bucket_length(lo + 1) == hi

    def test_largest_bucket_caps(self):
        top = LENGTH_BUCKETS[-1]
        assert bucket_length(top + 1) == top
        assert bucket_length(10 * top) == top

    def test_tiny_traces_land_in_smallest(self):
        assert bucket_length(0) == LENGTH_BUCKETS[0]
        assert bucket_length(1) == LENGTH_BUCKETS[0]

    def test_truncation_at_largest_bucket(self, city, monkeypatch):
        """A trace whose kept points exceed the largest bucket is
        truncated to it (shrunken buckets keep the test cheap)."""
        monkeypatch.setattr(batchpad, "LENGTH_BUCKETS", (4, 8))
        m = SegmentMatcher(net=city, use_native=False)
        rng = np.random.default_rng(1)
        tr = None
        while tr is None or len(tr.points) < 20:
            tr = generate_trace(city, "long", rng, noise_m=3.0,
                                min_route_edges=10)
        p = prepare_trace(city, m.grid, tr.points[:20], MatchParams(),
                          m.route_cache)
        assert p.T == 8
        assert p.num_kept <= 8
        # the truncated tail carries no verified dwell
        assert p.trailing_jitter_dwell_s == 0.0


class TestOccupancyMath:
    def test_pinned_waste_fixture(self):
        """The pinned synthetic-batch ratio: 10 + 50 kept points in a
        2-row T=64 batch -> 128 cells, waste exactly 1 - 60/128."""
        cells, occ, waste = occupancy_stats(60, rows=2, T=64)
        assert cells == 128
        assert occ == pytest.approx(60 / 128)
        assert waste == pytest.approx(0.53125)

    def test_empty_batch_is_zero_occupancy(self):
        cells, occ, waste = occupancy_stats(0, rows=0, T=64)
        assert cells == 0 and occ == 0.0 and waste == 1.0

    def test_kept_point_count_matches_prepared_batch(self, city):
        """kept_point_count over a packed batch == the sum of each
        trace's num_kept (pad rows/tails are all-SKIP)."""
        m = SegmentMatcher(net=city, use_native=False)
        rng = np.random.default_rng(2)
        prepared = []
        for i in range(3):
            tr = None
            while tr is None:
                tr = generate_trace(city, f"o{i}", rng, noise_m=3.0,
                                    min_route_edges=6)
            prepared.append(prepare_trace(city, m.grid, tr.points,
                                          MatchParams(), m.route_cache))
        for batch in pack_batches(prepared, pad_pow2=True):
            expect = sum(p.num_kept for p in batch.traces)
            assert kept_point_count(batch) == expect
            rows, T = batch.case.shape
            cells, occ, waste = occupancy_stats(expect, rows, T)
            assert 0.0 < occ < 1.0
            assert waste == pytest.approx(1.0 - expect / cells)


# ---------------------------------------------------------------------------
class TestCompileTelemetry:
    def test_episode_attribution_and_recompile_storm(self, caplog):
        """Direct listener feeds: a dispatch with a compile event is an
        episode; the SAME shape compiling again is a storm."""
        c0 = _counter("decode.compile.count")
        r0 = _counter("decode.compile.recompiles")
        with profiler.dispatch_span(8, 64, 8):
            profiler._on_event_duration(
                "/jax/core/compile/backend_compile_duration", 0.25)
        assert _counter("decode.compile.count") == c0 + 1
        assert _counter("decode.compile.recompiles") == r0
        # steady dispatch: no compile event -> no episode
        with profiler.dispatch_span(8, 64, 8):
            pass
        assert _counter("decode.compile.count") == c0 + 1
        # the same shape compiling AGAIN is the storm signal
        import logging
        with caplog.at_level(logging.WARNING, "reporter_tpu.obs"):
            with profiler.dispatch_span(8, 64, 8):
                profiler._on_event_duration(
                    "/jax/core/compile/backend_compile_duration", 0.1)
        assert _counter("decode.compile.recompiles") == r0 + 1
        assert any("recompile storm" in r.message
                   for r in caplog.records)
        snap = profiler.snapshot()
        (shape,) = snap["shapes"]
        assert shape["compiles"] == 2 and shape["dispatches"] == 3
        assert shape["steady"]["n"] == 1
        assert shape["compile_s"] == pytest.approx(0.35, abs=1e-6)

    def test_backend_switch_is_not_a_storm(self, monkeypatch):
        """A different decode backend compiling the same (B, T, K) is a
        NEW compiled shape, never a recompile storm (bench's pallas
        leg, operator A/Bs via REPORTER_TPU_DECODE)."""
        r0 = _counter("decode.compile.recompiles")
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        with profiler.dispatch_span(8, 64, 8):
            profiler._on_event_duration(
                "/jax/core/compile/backend_compile_duration", 0.1)
        monkeypatch.setenv("REPORTER_TPU_DECODE", "assoc")
        with profiler.dispatch_span(8, 64, 8):
            profiler._on_event_duration(
                "/jax/core/compile/backend_compile_duration", 0.1)
        assert _counter("decode.compile.recompiles") == r0
        backends = {s["backend"] for s in profiler.snapshot()["shapes"]}
        assert backends == {"scan", "assoc"}

    def test_failed_dispatch_records_nothing(self):
        """An aborted dispatch's wall is time-to-failure, not latency —
        it must not seed the shape table or the steady histograms."""
        with pytest.raises(RuntimeError):
            with profiler.dispatch_span(8, 64, 8):
                raise RuntimeError("device fell over")
        assert profiler.snapshot()["shapes"] == []
        # and a later clean dispatch still opens the shape normally
        with profiler.dispatch_span(8, 64, 8):
            pass
        (shape,) = profiler.snapshot()["shapes"]
        assert shape["dispatches"] == 1

    def test_unrelated_events_ignored(self):
        c0 = _counter("decode.compile.count")
        with profiler.dispatch_span(4, 16, 8):
            profiler._on_event_duration(
                "/jax/core/compile/jaxpr_trace_duration", 0.5)
        assert _counter("decode.compile.count") == c0

    def test_real_match_compiles_once_per_shape(self, city):
        """End to end: an identical second match_many adds ZERO compile
        episodes (the acceptance invariant obs_smoke asserts over
        HTTP)."""
        m = SegmentMatcher(net=city)
        rng = np.random.default_rng(5)
        reqs = []
        for i in range(3):
            tr = None
            while tr is None:
                tr = generate_trace(city, f"c{i}", rng, noise_m=3.0,
                                    min_route_edges=6)
            reqs.append({"uuid": tr.uuid, "trace": tr.points[:12]})
        out = m.match_many(reqs)
        assert all(r is not None for r in out)
        episodes = profiler.compile_count()
        out2 = m.match_many(reqs)
        assert all(r is not None for r in out2)
        assert profiler.compile_count() == episodes
        # and the chunk left a wide event with sane occupancy
        evs = profiler.recent_events()
        assert evs and 0.0 <= evs[-1]["padding_waste"] < 1.0
        assert evs[-1]["traces"] == 3


# ---------------------------------------------------------------------------
class TestWideEvents:
    def test_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv(profiler.ENV_RING, "16")
        profiler.reset()
        for i in range(50):
            profiler.chunk_event(bucket_T=16, K=8, traces=1, rows=1,
                                 kept_points=8, raw_points=10)
        assert len(profiler.recent_events(None)) == 16
        assert profiler.recent_events(0) == []

    def test_trace_id_joins_armed_requests(self):
        obs_trace.configure(True)
        try:
            with obs_trace.span("test.root") as root:
                profiler.chunk_event(bucket_T=16, K=8, traces=1, rows=1,
                                     kept_points=8, raw_points=10)
                trace_id = root.trace_id
        finally:
            obs_trace.configure(False)
        ev = profiler.recent_events(1)[0]
        assert ev["trace_id"] == trace_id

    def test_disarmed_events_carry_no_trace_id(self):
        profiler.chunk_event(bucket_T=16, K=8, traces=1, rows=1,
                             kept_points=8, raw_points=10)
        assert profiler.recent_events(1)[0]["trace_id"] is None

    def test_queue_depth_stamped(self):
        profiler.note_queue_depth(7)
        profiler.chunk_event(bucket_T=16, K=8, traces=1, rows=1,
                             kept_points=8, raw_points=10)
        assert profiler.recent_events(1)[0]["queue_depth"] == 7

    def test_occupancy_histogram_per_bucket(self):
        before = metrics.snapshot()["timers"].get("decode.occupancy.t64")
        n0 = before["count"] if before else 0
        profiler.chunk_event(bucket_T=64, K=8, traces=2, rows=2,
                             kept_points=60, raw_points=70)
        t = metrics.snapshot()["timers"]["decode.occupancy.t64"]
        assert t["count"] == n0 + 1

    def test_padding_waste_totals(self):
        assert profiler.padding_waste() is None
        profiler.chunk_event(bucket_T=64, K=8, traces=2, rows=2,
                             kept_points=60, raw_points=70)
        assert profiler.padding_waste() == pytest.approx(0.53125)


# ---------------------------------------------------------------------------
def _toy_batch(seed=3):
    """A hand-built 1-trace decode batch + its oracle path."""
    from reporter_tpu.matcher.cpu_ref import viterbi_decode_numpy
    from reporter_tpu.matcher.hmm import NORMAL, RESTART
    B, T, K = 1, 6, 3
    rng = np.random.default_rng(seed)
    dist = rng.uniform(0, 30, (B, T, K)).astype(np.float32)
    valid = np.ones((B, T, K), bool)
    gc = rng.uniform(5, 40, (B, T - 1)).astype(np.float32)
    route = rng.uniform(5, 80, (B, T - 1, K, K)).astype(np.float32)
    case = np.full((B, T), NORMAL, np.int32)
    case[:, 0] = RESTART
    batch = types.SimpleNamespace(dist_m=dist, valid=valid,
                                  route_m=route, gc_m=gc, case=case)
    path, _ = viterbi_decode_numpy(dist[0], valid[0], route[0], gc[0],
                                   case[0], 4.07, 3.0)
    return batch, path


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_oracle_equals_per_trace_oracle(seed):
    """The shadow sampler's row-vectorised oracle is the per-trace
    oracle, row for row: same paths, same scores, with RESTART, SKIP,
    invalid candidates and unreachable routes mixed in."""
    from reporter_tpu.matcher.cpu_ref import (viterbi_decode_numpy,
                                              viterbi_decode_numpy_batch)
    from reporter_tpu.matcher.hmm import NORMAL, RESTART, SKIP
    B, T, K = 9, 24, 4
    rng = np.random.default_rng(seed)
    dist = rng.uniform(0, 30, (B, T, K)).astype(np.float16)
    valid = rng.random((B, T, K)) > 0.1
    gc = rng.uniform(5, 40, (B, T - 1)).astype(np.float16)
    route = rng.uniform(5, 80, (B, T - 1, K, K)).astype(np.float16)
    route[rng.random(route.shape) < 0.1] = np.float16(np.inf)
    case = rng.choice([NORMAL, NORMAL, NORMAL, RESTART, SKIP],
                      size=(B, T)).astype(np.int32)
    case[:, 0] = RESTART
    case[-1] = SKIP  # an all-filler row
    paths, scores = viterbi_decode_numpy_batch(dist, valid, route, gc,
                                               case, 4.07, 3.0)
    for b in range(B):
        p1, s1 = viterbi_decode_numpy(dist[b], valid[b], route[b], gc[b],
                                      case[b], 4.07, 3.0)
        assert np.array_equal(paths[b], p1), b
        assert scores[b] == s1 or (np.isnan(scores[b]) and np.isnan(s1))


class TestShadowSampling:
    def test_agreeing_decode_has_no_mismatch(self, monkeypatch):
        monkeypatch.setenv(profiler.ENV_SHADOW, "1.0")
        batch, path = _toy_batch()
        m0 = _counter("decode.shadow.mismatch")
        s0 = _counter("decode.shadow.sampled")
        profiler.maybe_shadow(batch, path[None, :], 1, 4.07, 3.0)
        assert profiler.drain_shadow(30.0)
        assert _counter("decode.shadow.sampled") == s0 + 1
        assert _counter("decode.shadow.mismatch") == m0
        assert profiler.shadow_mismatches() == 0

    def test_doctored_decode_is_a_mismatch(self, monkeypatch):
        monkeypatch.setenv(profiler.ENV_SHADOW, "1.0")
        batch, path = _toy_batch()
        bad = path.copy()
        bad[2] = (bad[2] + 1) % 3  # a strictly worse state choice
        m0 = _counter("decode.shadow.mismatch")
        profiler.maybe_shadow(batch, bad[None, :], 1, 4.07, 3.0)
        assert profiler.drain_shadow(30.0)
        assert _counter("decode.shadow.mismatch") == m0 + 1
        assert profiler.shadow_mismatches() == 1

    def test_sampling_accumulator_is_deterministic(self, monkeypatch):
        monkeypatch.setenv(profiler.ENV_SHADOW, "0.5")
        batch, path = _toy_batch()
        c0 = _counter("decode.shadow.chunks")
        for _ in range(4):
            profiler.maybe_shadow(batch, path[None, :], 1, 4.07, 3.0)
            assert profiler.drain_shadow(30.0)
        assert _counter("decode.shadow.chunks") == c0 + 2

    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv(profiler.ENV_SHADOW, raising=False)
        batch, path = _toy_batch()
        c0 = _counter("decode.shadow.chunks")
        profiler.maybe_shadow(batch, path[None, :], 1, 4.07, 3.0)
        assert profiler.drain_shadow(5.0)
        assert _counter("decode.shadow.chunks") == c0

    def test_submit_failure_never_escapes_or_leaks(self, monkeypatch):
        """A pool-submit failure (thread exhaustion, shutdown) must not
        propagate into the serving drain lane, and must release the
        reserved pending slot."""
        monkeypatch.setenv(profiler.ENV_SHADOW, "1.0")

        def boom():
            raise RuntimeError("can't start new thread")
        monkeypatch.setattr(profiler, "_ensure_shadow_pool", boom)
        batch, path = _toy_batch()
        e0 = _counter("decode.shadow.errors")
        profiler.maybe_shadow(batch, path[None, :], 1, 4.07, 3.0)
        assert _counter("decode.shadow.errors") == e0 + 1
        assert profiler.shadow_stats()["pending"] == 0

    def test_tie_breaks_are_agreement(self, monkeypatch):
        """Two equal-quality paths (exact score tie) are NOT a
        mismatch — the device may break ties differently."""
        from reporter_tpu.matcher.hmm import NORMAL, RESTART
        monkeypatch.setenv(profiler.ENV_SHADOW, "1.0")
        B, T, K = 1, 3, 2
        # symmetric tensors: both states score identically everywhere
        dist = np.full((B, T, K), 5.0, np.float32)
        valid = np.ones((B, T, K), bool)
        gc = np.full((B, T - 1), 10.0, np.float32)
        route = np.full((B, T - 1, K, K), 10.0, np.float32)
        case = np.full((B, T), NORMAL, np.int32)
        case[:, 0] = RESTART
        batch = types.SimpleNamespace(dist_m=dist, valid=valid,
                                      route_m=route, gc_m=gc, case=case)
        other = np.array([[1, 1, 1]], np.int32)  # a different tie path
        m0 = _counter("decode.shadow.mismatch")
        profiler.maybe_shadow(batch, other, 1, 4.07, 3.0)
        assert profiler.drain_shadow(30.0)
        assert _counter("decode.shadow.mismatch") == m0


# ---------------------------------------------------------------------------
class TestServiceSurface:
    @pytest.fixture(scope="class")
    def server(self, city):
        from reporter_tpu.service.server import ReporterService, serve
        service = ReporterService(SegmentMatcher(net=city),
                                  threshold_sec=15, max_batch=16,
                                  max_wait_ms=5.0)
        httpd = serve(service, "127.0.0.1", 0)
        yield f"http://127.0.0.1:{httpd.server_address[1]}", service
        httpd.shutdown()

    def test_profile_action(self, city, server):
        base, service = server
        rng = np.random.default_rng(9)
        tr = None
        while tr is None:
            tr = generate_trace(city, "p0", rng, noise_m=3.0,
                                min_route_edges=6)
        req = urllib.request.Request(
            f"{base}/report",
            data=json.dumps({
                "uuid": tr.uuid, "trace": tr.points,
                "match_options": {"mode": "auto",
                                  "report_levels": [0, 1],
                                  "transition_levels": [0, 1]},
            }).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        with urllib.request.urlopen(f"{base}/profile") as r:
            assert r.status == 200
            prof = json.loads(r.read().decode())
        for key in ("shapes", "events", "totals", "shadow",
                    "queue_depth", "compile_episodes"):
            assert key in prof
        assert prof["events"], "no wide event after a /report"
        ev = prof["events"][-1]
        assert 0.0 <= ev["padding_waste"] < 1.0
        assert ev["bucket_T"] in LENGTH_BUCKETS

    def test_health_carries_shadow_block(self, server):
        base, _service = server
        with urllib.request.urlopen(f"{base}/health") as r:
            body = json.loads(r.read().decode())
        assert "shadow" in body
        assert set(body["shadow"]) >= {"fraction", "sampled",
                                       "mismatch"}


class TestFlightrecWideEvents:
    def test_dump_carries_last_wide_events(self, tmp_path, monkeypatch):
        from reporter_tpu.obs import flightrec
        monkeypatch.setenv(flightrec.ENV_VAR, str(tmp_path))
        flightrec._configure_env()
        try:
            for i in range(20):
                profiler.chunk_event(bucket_T=16, K=8, traces=1, rows=1,
                                     kept_points=8 + i, raw_points=20)
            path = flightrec.dump("test.wide")
            assert path is not None
            with open(path, encoding="utf-8") as f:
                post = json.load(f)
            assert len(post["wide_events"]) == 16  # the last 16
            assert post["wide_events"][-1]["kept_points"] == 27
        finally:
            monkeypatch.delenv(flightrec.ENV_VAR)
            flightrec._dir_from_env = False
            flightrec._dump_dir = None


# ---------------------------------------------------------------------------
def _load_tool(name):
    """Import a tools/*.py script as a module (tools/ is not a
    package)."""
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ledger_mod():
    from reporter_tpu.obs import ledger
    return ledger


@pytest.fixture(scope="module")
def gate_mod():
    return _load_tool("perf_gate")


class TestPerfLedger:
    def test_entry_from_bench_parses_metric(self, ledger_mod):
        parsed = {
            "metric": "x (columnar prep+decode+assemble+report-serialise,"
                      " T=64, K=8, platform=cpu, decode=scan) y",
            "value": 8000.0, "vs_baseline": 20.0,
            "stages": {"prep": 0.03, "decode_wait": 0.01,
                       "assemble": 0.01, "report": 0.02, "total": 0.06,
                       "pipelined": True},
            "baseline": {"traces_per_sec": 400.0, "n_traces": 128},
        }
        e = ledger_mod.entry_from_bench(parsed, "f.json", "t", "bench")
        assert e["platform"] == "cpu" and e["decode"] == "scan"
        assert e["scope"] == "full" and e["pipelined"] is True
        assert e["stage_shares"]["prep"] == pytest.approx(0.5)
        assert e["stage_shares"]["report"] == pytest.approx(0.3333)

    def test_legacy_scope_drops_report_share(self, ledger_mod):
        parsed = {
            "metric": "x (prep+decode+assemble+report, T=64, K=8, "
                      "platform=cpu, decode=scan) y",
            "vs_baseline": 18.0, "value": 7000.0,
            "stages": {"prep": 0.02, "report": 0.002, "total": 0.04},
            "baseline": {"traces_per_sec": 400.0, "n_traces": 128},
        }
        e = ledger_mod.entry_from_bench(parsed, "f.json", "t", "bench")
        # PR 4 widened the report stage's scope; legacy shares of it
        # must not be gated against
        assert "report" not in e["stage_shares"]
        assert "prep" in e["stage_shares"]

    def test_smoke_scale_detected(self, ledger_mod):
        parsed = {"metric": "x (… platform=cpu, decode=scan)",
                  "vs_baseline": 0.6, "value": 90.0,
                  "stages": {"prep": 0.01, "total": 0.5,
                             "pipelined": True},
                  "baseline": {"traces_per_sec": 160.0, "n_traces": 8}}
        e = ledger_mod.entry_from_bench(parsed, "s.json", "t", "bench")
        assert e["scope"] == "smoke"

    def test_seed_covers_every_artifact(self, ledger_mod):
        entries = ledger_mod.seed_entries(REPO)
        sources = {e["source"] for e in entries}
        assert {"BENCH_r04.json", "BENCH_r05.json",
                "BENCH_DEV_r06.json", "MULTICHIP_r05.json"} <= sources
        ratios = [e for e in entries if e["vs_baseline"] is not None]
        assert len(ratios) >= 6
        # context notes carried where the artifact recorded box drift
        r06 = [e for e in entries if e["label"] == "dev_r06"][0]
        assert "2x" in (r06["context"] or "")

    def test_committed_ledger_covers_the_seed(self, ledger_mod):
        """Every entry a fresh seed derives from the checked-in
        artifacts is present in the committed LEDGER.jsonl (regenerate
        or re-append with `perf_ledger.py` when adding an artifact).
        Containment, not equality: the documented workflow APPENDS
        live entries (e.g. smoke-scope history that makes the CI gate
        bind), and those never come from an artifact."""
        committed = ledger_mod.load_ledger(
            os.path.join(REPO, "LEDGER.jsonl"))
        for entry in ledger_mod.seed_entries(REPO):
            assert entry in committed, entry["label"]


class TestPerfGate:
    def _entries(self, ledger_mod):
        return ledger_mod.seed_entries(REPO)

    def test_clean_candidate_passes(self, ledger_mod, gate_mod):
        entries = self._entries(ledger_mod)
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": 19.0, "pipelined": False,
                "stage_shares": {"prep": 0.4}, "kind": "bench"}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert passed, verdict

    def test_regressed_ratio_fails(self, ledger_mod, gate_mod):
        entries = self._entries(ledger_mod)
        import statistics
        median = statistics.median(
            e["vs_baseline"] for e in gate_mod.comparable_pool(
                entries, "cpu", "full"))
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": round(median * 0.8, 2),
                "pipelined": False, "stage_shares": None,
                "kind": "bench"}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert not passed
        assert verdict["failures"][0]["check"] == "ratio"

    def test_drift_control_rescues_below_floor_ratio(self, ledger_mod,
                                                     gate_mod):
        """A candidate below the cross-box floor passes the ratio check
        iff its same-box control is ALSO below the floor (the box
        provably can't reach the median) and the candidate is within
        tolerance of the control."""
        entries = self._entries(ledger_mod)
        import statistics
        median = statistics.median(
            e["vs_baseline"] for e in gate_mod.comparable_pool(
                entries, "cpu", "full"))
        low = round(median * 0.8, 2)
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": low, "pipelined": False,
                "stage_shares": None, "kind": "bench",
                "control_vs_baseline": round(median * 0.78, 2)}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert passed, verdict
        assert "ratio_drift_control" in verdict

    def test_drift_control_no_leniency_on_healthy_box(self, ledger_mod,
                                                      gate_mod):
        """A control at/above the floor proves the box is fine — the
        slow candidate is a code regression and still fails."""
        entries = self._entries(ledger_mod)
        import statistics
        median = statistics.median(
            e["vs_baseline"] for e in gate_mod.comparable_pool(
                entries, "cpu", "full"))
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": round(median * 0.8, 2),
                "pipelined": False, "stage_shares": None,
                "kind": "bench",
                "control_vs_baseline": round(median * 1.0, 2)}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert not passed
        assert verdict["failures"][0]["check"] == "ratio"

    def test_drift_control_bounds_the_regression(self, ledger_mod,
                                                 gate_mod):
        """Even on a drifted box the candidate must stay within
        tolerance of the control — drift never hides a real loss."""
        entries = self._entries(ledger_mod)
        import statistics
        median = statistics.median(
            e["vs_baseline"] for e in gate_mod.comparable_pool(
                entries, "cpu", "full"))
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": round(median * 0.5, 2),
                "pipelined": False, "stage_shares": None,
                "kind": "bench",
                "control_vs_baseline": round(median * 0.8, 2)}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert not passed
        assert verdict["failures"][0]["check"] == "ratio"

    def test_grown_stage_share_fails(self, ledger_mod, gate_mod):
        entries = self._entries(ledger_mod)
        cand = {"source": "c", "platform": "cpu", "scope": "full",
                "vs_baseline": 19.0, "pipelined": False,
                "stage_shares": {"prep": 0.95}, "kind": "bench"}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert not passed
        assert any(f["check"] == "share" and f["stage"] == "prep"
                   for f in verdict["failures"])

    def test_unmatched_scope_passes_with_note(self, ledger_mod,
                                              gate_mod):
        entries = self._entries(ledger_mod)
        cand = {"source": "smoke", "platform": "cpu", "scope": "smoke",
                "vs_baseline": 0.5, "pipelined": True,
                "stage_shares": None, "kind": "bench"}
        passed, verdict = gate_mod.gate(cand, entries, 0.15, 0.2, False)
        assert passed and "note" in verdict
        # --require-history makes the empty pool binding
        passed, _ = gate_mod.gate(cand, entries, 0.15, 0.2, True)
        assert not passed


# ---------------------------------------------------------------------------
class TestHeartbeatFields:
    def test_heartbeat_carries_device_vitals(self, tmp_path,
                                             monkeypatch, caplog):
        import logging
        from reporter_tpu.streaming.anonymiser import Anonymiser, TileSink
        from reporter_tpu.streaming.formatter import Formatter
        from reporter_tpu.streaming.worker import StreamWorker
        monkeypatch.setenv("REPORTER_TPU_HEARTBEAT_S", "0.0001")
        profiler.chunk_event(bucket_T=64, K=8, traces=2, rows=2,
                             kept_points=60, raw_points=70)
        worker = StreamWorker(
            Formatter.from_config(r",sv,\|,0,1,2,3,4"),
            lambda trace: None,
            Anonymiser(TileSink(str(tmp_path)), 1, 3600, source="t"),
            flush_interval_s=1e9)
        with caplog.at_level(logging.INFO, "reporter_tpu.streaming"):
            worker._hb_last -= 1.0
            worker._maybe_heartbeat()
        lines = [r.message for r in caplog.records
                 if r.message.startswith("heartbeat ")]
        assert lines
        payload = json.loads(lines[0][len("heartbeat "):])
        assert payload["padding_waste"] == pytest.approx(0.5312, abs=1e-3)
        assert payload["compile_count"] == 0
        assert payload["shadow_mismatches"] == 0
