"""Load-management tests (ISSUE 15): the admission gate, the pressure
ladder and its rung effects, the bounded dispatcher queue, EWMA
latency-targeted micro-batching, streaming backpressure, and the
dead-letter drainer's seeded backoff jitter."""
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from reporter_tpu.service import admission
from reporter_tpu.service.admission import (AdmissionGate, Overload,
                                            PressureLadder, RUNGS,
                                            WindowedQuantile,
                                            retry_after_s)
from reporter_tpu.service.dispatch import BatchDispatcher
from reporter_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh_admission():
    """Every test starts at pressure zero with no process-wide ladder
    (and leaves none behind for the rest of the suite)."""
    admission._reset_module()
    yield
    admission._reset_module()


class StubDispatcher:
    """Duck-typed dispatcher for gate unit tests."""

    def __init__(self, depth=0, ewma=None, queue_max=0, max_batch=32):
        self.depth = depth
        self.ewma = ewma
        self.queue_max = queue_max
        self.max_batch = max_batch

    def queue_depth(self):
        return self.depth

    def service_ewma_s(self):
        return self.ewma


class TestRetryAfter:
    def test_clamps(self):
        assert retry_after_s(0, None) == 1
        assert retry_after_s(100, None) == 1      # no estimate yet
        assert retry_after_s(10, 0.5) == 5
        assert retry_after_s(1, 0.001) == 1       # floor
        assert retry_after_s(10_000, 1.0) == 30   # cap


class TestWindowedQuantile:
    def test_breach_then_recovery(self):
        """The windowed p99 must FORGET a bad minute — the property the
        lifetime histogram p99 lacks and admission control needs."""
        r = metrics.Registry()
        w = WindowedQuantile(r)
        for _ in range(50):
            r.observe("stage", 0.9)
        p99 = w.update(["stage"])["stage"]
        assert p99 is not None and p99 > 0.5
        # idle window: no new observations -> None, never a breach
        assert w.update(["stage"])["stage"] is None
        # recovery window: fast observations only -> small p99, even
        # though the lifetime histogram still remembers the 0.9s tail
        for _ in range(50):
            r.observe("stage", 0.001)
        p99 = w.update(["stage"])["stage"]
        assert p99 is not None and p99 < 0.01
        lifetime = r.snapshot()["timers"]["stage"]["p99_s"]
        assert lifetime > 0.5  # the contrast the class exists for

    def test_unknown_stage_is_none(self):
        w = WindowedQuantile(metrics.Registry())
        assert w.update(["nope"])["nope"] is None


class TestPressureLadder:
    def test_hysteresis_and_rung_effects(self):
        import numpy as np

        from reporter_tpu.matcher import batchpad
        from reporter_tpu.matcher import matcher as matcher_mod
        from reporter_tpu.obs import profiler

        def planned_t(raw):  # the T a lone trace of ``raw`` points gets
            return batchpad.plan_chunks(np.array([raw]), np.arange(1),
                                        128, None)[0][0]
        clk = [0.0]
        lad = PressureLadder(hold_s=1.0, clock=lambda: clk[0])
        assert lad.observe(True) == 0          # dwell 0 < hold
        clk[0] = 1.0
        assert lad.observe(True) == 1          # held for hold_s
        assert profiler.shadow_stats()["suspended"]
        clk[0] = 1.5
        assert lad.observe(True) == 1          # one rung per hold
        clk[0] = 2.0
        assert lad.observe(True) == 2
        assert not admission.allow_request_trace()
        clk[0] = 3.0
        assert lad.observe(True) == 3
        assert planned_t(100) == 256  # classed at its ladder bucket
        clk[0] = 4.0
        assert lad.observe(True) == 4
        assert matcher_mod._pressure_oracle
        clk[0] = 5.0
        assert lad.observe(True) == 4          # capped at the top rung
        # calm: stepping back up needs 2x the hold
        assert lad.observe(False) == 4
        clk[0] = 6.5
        assert lad.observe(False) == 4         # 1.5 < 2.0
        clk[0] = 7.0
        assert lad.observe(False) == 3
        assert not matcher_mod._pressure_oracle   # oracle rung left
        assert planned_t(100) == 256  # coarse still held
        clk[0] = 9.0
        assert lad.observe(False) == 2
        assert planned_t(100) == 128
        assert not admission.allow_request_trace()  # trace still shed
        clk[0] = 11.0
        assert lad.observe(False) == 1
        assert admission.allow_request_trace()
        assert profiler.shadow_stats()["suspended"]  # last rung held
        clk[0] = 13.0
        assert lad.observe(False) == 0
        assert not profiler.shadow_stats()["suspended"]
        assert not matcher_mod._pressure_oracle
        assert lad.transitions == 8
        snap = lad.snapshot()
        assert snap["state"] == "normal" and snap["rungs"] == list(RUNGS)

    def test_flap_resistance(self):
        """Alternating pressure samples faster than the hold never
        move the ladder."""
        clk = [0.0]
        lad = PressureLadder(hold_s=1.0, clock=lambda: clk[0])
        for i in range(40):
            clk[0] += 0.3
            lad.observe(i % 2 == 0)
        assert lad.level == 0 and lad.transitions == 0


class TestAdmissionGate:
    def _gate(self, dispatcher, **kw):
        clk = kw.pop("clk", [0.0])
        return AdmissionGate(dispatcher, clock=lambda: clk[0], **kw), clk

    def test_queue_hard_bound(self):
        gate, _ = self._gate(StubDispatcher(depth=5, ewma=0.01,
                                            queue_max=5))
        before = metrics.default.counter("admission.shed.queue")
        verdict = gate.admit()
        assert isinstance(verdict, Overload)
        assert verdict.reason == "queue" and verdict.retry_after_s >= 1
        assert metrics.default.counter("admission.shed.queue") \
            == before + 1

    def test_deadline_shed(self, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_SLO_MS", "service.handle=100")
        # predicted wait 10 * 20ms = 200ms > 0.5 * 100ms budget
        gate, _ = self._gate(StubDispatcher(depth=10, ewma=0.02))
        verdict = gate.admit()
        assert verdict is not None and verdict.reason == "queue"
        # same depth, fast service: 10 * 1ms = 10ms -> admitted
        gate2, _ = self._gate(StubDispatcher(depth=10, ewma=0.001))
        assert gate2.admit() is None
        gate2.release()

    def test_windowed_slo_breach_and_recovery(self, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_SLO_MS", "service.handle=50")
        reg = metrics.Registry()
        clk = [0.0]
        gate = AdmissionGate(StubDispatcher(), clock=lambda: clk[0],
                             registry=reg)
        for _ in range(30):
            reg.observe("service.handle", 0.5)  # 10x over budget
        clk[0] = 1.0  # past the eval interval -> refresh
        verdict = gate.admit()
        assert verdict is not None and verdict.reason == "slo"
        # load drops: a fast window clears the breach (the lifetime
        # histogram still remembers — the windowed sensor must not)
        for _ in range(30):
            reg.observe("service.handle", 0.001)
        clk[0] = 2.0
        assert gate.admit() is None
        gate.release()

    def test_inflight_cap_and_release(self):
        gate, _ = self._gate(StubDispatcher(), inflight_max=1)
        assert gate.admit() is None
        verdict = gate.admit()
        assert verdict is not None and verdict.reason == "inflight"
        gate.release()
        assert gate.admit() is None
        gate.release()

    def test_snapshot_shape(self):
        gate, _ = self._gate(StubDispatcher(depth=3, ewma=0.004),
                             inflight_max=7)
        snap = gate.snapshot()
        assert snap["armed"] and snap["inflight_max"] == 7
        assert snap["queue_depth"] == 3
        assert set(snap["shed"]) == {"queue", "slo", "inflight"}

    def test_armed_reads_env(self, monkeypatch):
        monkeypatch.delenv("REPORTER_TPU_ADMISSION", raising=False)
        assert not admission.armed()
        monkeypatch.setenv("REPORTER_TPU_ADMISSION", "1")
        assert admission.armed()
        monkeypatch.setenv("REPORTER_TPU_ADMISSION", "off")
        assert not admission.armed()


def _results(batch):
    return [{"ok": True} for _ in batch]


class TestBoundedQueue:
    """Deterministic by construction: a "plug" batch occupies the
    dispatch loop (match_many blocks on an event), so the bounded
    queue can be filled EXACTLY — nothing drains until release."""

    def _plugged_dispatcher(self, **kw):
        release = threading.Event()

        def blocked(batch):
            release.wait(10.0)
            return _results(batch)

        d = BatchDispatcher(blocked, max_batch=2, max_wait_ms=5.0,
                            **kw)
        from reporter_tpu.service.dispatch import _Slot
        d._queue.put(_Slot({"uuid": "plug"}))
        deadline = time.monotonic() + 5.0
        while d._in_service == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert d._in_service == 1  # the loop is busy; fills are exact
        return d, release

    def test_reject_policy_sheds_new(self):
        from reporter_tpu.service.dispatch import _Slot
        d, release = self._plugged_dispatcher(queue_max=2,
                                              queue_policy="reject")
        try:
            fills = [_Slot({"uuid": f"q{i}"}) for i in range(2)]
            for slot in fills:
                d._enqueue_nowait(slot)
            assert d._queue.qsize() == 2
            before = metrics.default.counter("dispatch.queue.rejected")
            with pytest.raises(Overload) as exc:
                d.submit({"uuid": "overflow"}, timeout=1.0)
            assert exc.value.reason == "queue"
            assert exc.value.retry_after_s >= 1
            assert metrics.default.counter("dispatch.queue.rejected") \
                == before + 1
            assert d._queue.qsize() == 2  # the queued work survived
        finally:
            release.set()
            assert d.close()

    def test_oldest_policy_evicts_queued_waiter(self):
        from reporter_tpu.service.dispatch import _Slot
        d, release = self._plugged_dispatcher(queue_max=1,
                                              queue_policy="oldest")
        try:
            oldest = _Slot({"uuid": "old"})
            d._enqueue_nowait(oldest)
            before = metrics.default.counter("dispatch.queue.evicted")
            fresh = _Slot({"uuid": "fresh"})
            d._enqueue_nowait(fresh)  # full -> displaces "old"
            assert metrics.default.counter("dispatch.queue.evicted") \
                == before + 1
            # the displaced waiter was woken LOUDLY with the Overload
            assert oldest.event.is_set()
            assert isinstance(oldest.error, Overload)
            assert oldest.error.reason == "queue"
            assert fresh.error is None  # freshest work won the slot
        finally:
            release.set()
            assert d.close()
        assert fresh.event.wait(5.0)  # drained by close(), not lost
        assert fresh.result is not None

    def test_submit_many_blocking_backpressure(self):
        d, release = self._plugged_dispatcher(queue_max=2)
        try:
            with pytest.raises((Overload, TimeoutError)):
                d.submit_many([{"uuid": f"t{i}"} for i in range(6)],
                              timeout=0.3)
            assert metrics.default.counter("dispatch.queue.waits") >= 1
        finally:
            release.set()
            d.close()

    def test_unbounded_when_zero(self):
        d = BatchDispatcher(_results, max_batch=4, queue_max=0)
        try:
            out = d.submit_many([{"uuid": f"t{i}"} for i in range(64)],
                                timeout=10.0)
            assert len(out) == 64
        finally:
            d.close()


class TestLatencyBudget:
    def test_effective_cap(self):
        d = BatchDispatcher(_results, max_batch=64,
                            latency_budget_ms=100.0)
        try:
            assert d._effective_cap() == 64        # no EWMA yet
            d._ewma_per_trace = 0.01
            assert d._effective_cap() == 10        # 100ms / 10ms
            d._ewma_per_trace = 0.5
            assert d._effective_cap() == 1         # floor: progress
            d._ewma_per_trace = 0.0001
            assert d._effective_cap() == 64        # capped at max_batch
        finally:
            d.close()

    def test_budget_zero_keeps_fixed_batching(self):
        d = BatchDispatcher(_results, max_batch=64,
                            latency_budget_ms=0.0)
        try:
            d._ewma_per_trace = 10.0
            assert d._effective_cap() == 64
        finally:
            d.close()

    def test_ewma_updates_from_batches(self):
        d = BatchDispatcher(_results, max_batch=8)
        try:
            d._note_service_time(0.8, 8)
            first = d.service_ewma_s()
            assert first == pytest.approx(0.1)
            d._note_service_time(0.08, 8)
            assert d.service_ewma_s() < first  # EWMA moved toward fast
        finally:
            d.close()

    def test_batches_shrink_under_budget(self):
        """Integration: with a slow matcher and a budget, drained
        batches stay at the EWMA cap instead of max_batch."""
        sizes = []

        def slow(batch):
            sizes.append(len(batch))
            time.sleep(0.02 * len(batch))
            return _results(batch)

        d = BatchDispatcher(slow, max_batch=32, max_wait_ms=50.0,
                            latency_budget_ms=60.0)
        try:
            d.submit_many([{"uuid": f"w{i}"} for i in range(4)],
                          timeout=10.0)  # warm the EWMA (~20ms/trace)
            d.submit_many([{"uuid": f"t{i}"} for i in range(24)],
                          timeout=30.0)
            # after warm-up the cap is ~60/20 = 3 traces per batch
            assert max(sizes[1:]) <= 8
            assert metrics.default.counter(
                "batch.latency.capped_batches") > 0
        finally:
            d.close()


class TestQueueDepthGauges:
    def test_named_gauges_and_fork_reset(self):
        from reporter_tpu.obs import profiler
        profiler._reset_queue_depths()  # earlier tests' dispatchers
        profiler.note_queue_depth(4, name="svc-a")
        profiler.note_queue_depth(9, name="svc-b")
        assert profiler.queue_depth("svc-a") == 4
        assert profiler.queue_depth() == 9          # max across gauges
        assert profiler.queue_depths() == {"svc-a": 4, "svc-b": 9}
        snap = profiler.snapshot()
        assert snap["queue_depth"] == 9
        assert snap["queue_depths"]["svc-b"] == 9
        # the forksafe hook: a child must not inherit these
        profiler._reset_queue_depths()
        assert profiler.queue_depth() == 0
        assert profiler.queue_depths() == {}

    def test_dispatcher_notes_under_own_name(self):
        from reporter_tpu.obs import profiler
        profiler._reset_queue_depths()
        d = BatchDispatcher(_results, max_batch=4, name="gauge-test")
        try:
            d.submit({"uuid": "x"}, timeout=5.0)
            assert "gauge-test" in profiler.queue_depths()
        finally:
            d.close()


class TestServiceIntegration:
    @pytest.fixture(scope="class")
    def city(self):
        from reporter_tpu.synth import build_grid_city
        return build_grid_city(rows=8, cols=8, spacing_m=200.0, seed=7,
                               service_road_fraction=0.0,
                               internal_fraction=0.0)

    def _request(self, city, seed):
        import numpy as np

        from reporter_tpu.synth import generate_trace
        rng = np.random.default_rng(seed)
        tr = None
        while tr is None:
            tr = generate_trace(city, f"adm-{seed}", rng, noise_m=3.0)
        return {"uuid": tr.uuid, "trace": tr.points,
                "match_options": {"mode": "auto",
                                  "report_levels": [0, 1],
                                  "transition_levels": [0, 1]}}

    def test_armed_service_builds_gate_and_health_blocks(
            self, city, monkeypatch):
        from reporter_tpu.matcher import SegmentMatcher
        from reporter_tpu.service.server import ReporterService
        monkeypatch.setenv("REPORTER_TPU_ADMISSION", "1")
        service = ReporterService(SegmentMatcher(net=city),
                                  threshold_sec=15, max_batch=8)
        try:
            assert service.admission is not None
            code, body = service.handle(self._request(city, 1))
            assert code == 200
            code, body = service.health()
            health = json.loads(body)
            assert health["admission"]["armed"] is True
            assert health["pressure"]["state"] == "normal"
        finally:
            service.dispatcher.close()

    def test_unarmed_service_has_no_gate(self, city, monkeypatch):
        from reporter_tpu.matcher import SegmentMatcher
        from reporter_tpu.service.server import ReporterService
        monkeypatch.delenv("REPORTER_TPU_ADMISSION", raising=False)
        service = ReporterService(SegmentMatcher(net=city),
                                  threshold_sec=15, max_batch=8)
        try:
            assert service.admission is None
            health = json.loads(service.health()[1])
            assert health["admission"] == {"armed": False}
            assert health["pressure"]["level"] == 0
        finally:
            service.dispatcher.close()

    def test_http_429_carries_retry_after(self, city):
        from reporter_tpu.matcher import SegmentMatcher
        from reporter_tpu.service.server import ReporterService, serve

        class AlwaysShed:
            def admit(self):
                metrics.count("admission.shed.queue")
                return Overload("queue", 7.0)

            def release(self):
                pass

            def tick(self):
                pass

            def snapshot(self):
                return {"armed": True}

        service = ReporterService(SegmentMatcher(net=city),
                                  threshold_sec=15, max_batch=8)
        service.admission = AlwaysShed()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        httpd = serve(service, "127.0.0.1", port)
        try:
            q = urllib.parse.urlencode(
                {"json": json.dumps(self._request(city, 2))})
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/report?{q}")
            err = exc.value
            assert err.code == 429
            assert err.headers.get("Retry-After") == "7"
            body = json.loads(err.read())
            assert body["error"] == "overloaded"
            assert body["reason"] == "queue"
        finally:
            httpd.shutdown()
            service.dispatcher.close()

    def test_city_routed_requests_hit_their_own_gate(self, city):
        """A ``city=`` request must be shed by THAT city's gate — the
        front-door gate only watches the default dispatcher, and a
        city stack's overload would otherwise never shed at all."""
        from reporter_tpu.matcher import SegmentMatcher
        from reporter_tpu.service.server import ReporterService

        inner = ReporterService(SegmentMatcher(net=city),
                                threshold_sec=15, max_batch=8)

        class ShedGate:
            released = 0

            def admit(self):
                return Overload("queue", 11.0)

            def release(self):
                ShedGate.released += 1

        inner.admission = ShedGate()

        class Entry:
            service = inner

        class FakeRegistry:
            def acquire(self, name):
                assert name == "metro"
                return Entry()

            def release(self, entry):
                pass

        outer = ReporterService(SegmentMatcher(net=city),
                                threshold_sec=15, max_batch=8)
        outer.cities = FakeRegistry()
        try:
            req = dict(self._request(city, 9), city="metro")
            code, body = outer.handle(req)
            assert code == 429
            parsed = json.loads(body)
            assert parsed["reason"] == "queue"
            assert parsed["retry_after_s"] == 11.0
            assert ShedGate.released == 0  # shed never holds a slot
            # and an admitting city gate serves, releasing its slot
            inner.admission = None
            code, _body = outer.handle(req)
            assert code == 200
        finally:
            inner.dispatcher.close()
            outer.dispatcher.close()

    def test_pressure_oracle_rung_serves_identically(self, city):
        from reporter_tpu.matcher import SegmentMatcher
        from reporter_tpu.matcher import matcher as matcher_mod
        matcher = SegmentMatcher(net=city)
        req = self._request(city, 3)
        want = matcher.Match(json.dumps(req))
        before = metrics.default.counter("pressure.oracle_chunks")
        matcher_mod.set_pressure_oracle(True)
        try:
            got = matcher.Match(json.dumps(req))
        finally:
            matcher_mod.set_pressure_oracle(False)
        assert got == want
        assert metrics.default.counter("pressure.oracle_chunks") \
            > before


class TestBackpressure:
    def test_governor_thresholds_and_bounds(self):
        from reporter_tpu.streaming.backpressure import (
            SHED_FACTOR, BackpressureGovernor)
        g = BackpressureGovernor(latency_high_s=0.1, depth_high=4,
                                 max_delay_s=0.05)
        assert g.offer_delay() == 0.0 and not g.should_shed()
        g.note_flush(10, 0.5, 0, 0)          # 50ms/trace: calm
        assert g.offer_delay() == 0.0
        g.ewma_s = 0.2                        # 2x threshold
        assert 0.0 < g.offer_delay() <= 0.05
        assert not g.should_shed()
        g.ewma_s = 0.1 * SHED_FACTOR          # at the shed point
        assert g.offer_delay() == 0.05        # clamped at the bound
        assert g.should_shed()
        g.ewma_s = None
        g.note_flush(1, 0.0, 1, 20)           # depth 20 = 5x threshold
        assert g.should_shed()
        snap = g.snapshot()
        assert snap["shedding"] and snap["requeue_depth"] == 20

    def test_disabled_by_env(self, monkeypatch):
        from reporter_tpu.streaming.backpressure import \
            BackpressureGovernor
        monkeypatch.setenv("REPORTER_TPU_BACKPRESSURE", "0")
        g = BackpressureGovernor(latency_high_s=0.001)
        g.ewma_s = 100.0
        assert g.offer_delay() == 0.0 and not g.should_shed()

    def test_batcher_sheds_report_ready_sessions(self, tmp_path):
        from reporter_tpu.core.types import Point
        from reporter_tpu.streaming.backpressure import \
            BackpressureGovernor
        from reporter_tpu.streaming.batcher import PointBatcher
        g = BackpressureGovernor(latency_high_s=0.001, depth_high=1)
        g.ewma_s = 1.0  # pinned severe pressure
        assert g.should_shed()
        spool = str(tmp_path / ".traces")
        batcher = PointBatcher(lambda body: None, lambda k, s: None,
                               deadletter_dir=spool, governor=g)
        before = metrics.default.counter("backpressure.shed")
        t0 = 1700000000
        for i in range(12):
            batcher.process("veh-1", Point(lat=0.001 * i, lon=0.0,
                                           time=t0 + 30 * i,
                                           accuracy=5.0),
                            (t0 + 30 * i) * 1000)
        assert metrics.default.counter("backpressure.shed") \
            == before + 1
        assert not batcher.pending          # never queued
        # the shed session restarted from scratch (its spooled points
        # are gone; later points opened a fresh, small batch)
        assert len(batcher.store["veh-1"].points) < 10
        files = [f for f in os.listdir(spool) if f.endswith(".json")]
        assert len(files) == 1
        body = json.loads((tmp_path / ".traces" / files[0]).read_text())
        assert body["uuid"] == "veh-1" and len(body["trace"]) >= 10

    def test_requeue_depth_tracked(self):
        from reporter_tpu.streaming.batcher import PointBatcher
        batcher = PointBatcher(lambda body: None, lambda k, s: None,
                               retry_budget=2)
        from reporter_tpu.streaming.batcher import Batch
        from reporter_tpu.core.types import Point
        b = Batch(Point(lat=0.0, lon=0.0, time=1.0, accuracy=5.0))
        batcher.store["veh-2"] = b
        batcher._submit_failed("veh-2", b)
        assert len(batcher._retrying) == 1
        assert batcher.governor.requeue_depth == 0  # fed at flush time
        batcher._flush_due([])
        # empty flush does not feed the governor; simulate the real
        # path: a successful response clears the retry entry
        b.retries = 0
        batcher._retrying.pop("veh-2", None)
        assert len(batcher._retrying) == 0


class TestDrainerJitter:
    def _drainer(self, root, seed):
        from reporter_tpu.streaming.drainer import DeadLetterDrainer
        clk = [100.0]
        d = DeadLetterDrainer(
            str(root), trace_root=str(root / ".traces"),
            submit=lambda body: None,       # always fails -> backoff
            interval_s=0.0, max_attempts=10, base_backoff_s=1.0,
            max_backoff_s=60.0, jitter_seed=seed,
            clock=lambda: clk[0])
        return d, clk

    def _spool_one(self, root):
        from reporter_tpu.utils import spool
        os.makedirs(str(root / ".traces"), exist_ok=True)
        spool.write(str(root / ".traces"), "trace-1-000001.veh.json",
                    json.dumps({"uuid": "veh", "trace": []}))

    def test_deterministic_by_seed(self, tmp_path):
        delays = []
        for sub, seed in (("a", 42), ("b", 42), ("c", 43)):
            root = tmp_path / sub
            root.mkdir()
            self._spool_one(root)
            d, clk = self._drainer(root, seed)
            run = []
            for _ in range(4):
                d.maybe_drain()
                due = next(iter(d._due.values()))
                run.append(round(due - clk[0], 9))
                clk[0] = due + 0.001
            delays.append(run)
        assert delays[0] == delays[1]       # same seed, same schedule
        assert delays[0] != delays[2]       # different seed, different

    def test_jitter_bounds(self, tmp_path):
        self._spool_one(tmp_path)
        d, clk = self._drainer(tmp_path, 7)
        for attempt in range(1, 5):
            d.maybe_drain()
            due = next(iter(d._due.values()))
            base = min(1.0 * 2.0 ** (attempt - 1), 60.0)
            delay = due - clk[0]
            assert base <= delay <= base * 1.25
            clk[0] = due + 0.001

    def test_jitter_off(self, tmp_path):
        from reporter_tpu.streaming.drainer import DeadLetterDrainer
        self._spool_one(tmp_path)
        clk = [0.0]
        d = DeadLetterDrainer(
            str(tmp_path), trace_root=str(tmp_path / ".traces"),
            submit=lambda body: None, interval_s=0.0,
            base_backoff_s=1.0, backoff_jitter=0.0, jitter_seed=1,
            clock=lambda: clk[0])
        d.maybe_drain()
        assert next(iter(d._due.values())) == pytest.approx(1.0)
