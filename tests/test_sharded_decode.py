"""Device-mesh decode scale-out and the chunk plan.

The contract under test is BIT-IDENTITY: the 1-D ``("data",)`` decode
mesh carries no collective, so the sharded scan decode must equal the
single-device scan backend bit for bit — same Viterbi paths, same
/report bytes — at every forced host-device count. Subprocess legs pin
it at N∈{1,2,8} (the device count is fixed at backend init, so each N
is its own interpreter); in-process tests cover the conftest 8-device
mesh, the rows-not-divisible-by-mesh chunk, all-SKIP filler rows, the
chunk plan, and the knobs/gates.
"""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from reporter_tpu import ops
from reporter_tpu.matcher import MatchParams, SegmentMatcher
from reporter_tpu.matcher import batchpad
from reporter_tpu.matcher.batchpad import (LENGTH_BUCKETS, bucket_ladder,
                                           plan_chunks)
from reporter_tpu.matcher.matcher import (MatchRuns, _decode_chunk,
                                          match_batch_default,
                                          render_segments_json)
from reporter_tpu.obs import profiler
from reporter_tpu.synth import build_grid_city, generate_trace
from reporter_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_mesh_cache():
    ops.reset_sharded_cache()
    yield
    ops.reset_sharded_cache()


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=8, cols=8, spacing_m=200.0, seed=17)


def _mixed_reqs(city, n=5, seed=23, max_edges=10):
    rng = np.random.default_rng(seed)
    reqs = []
    while len(reqs) < n:
        tr = generate_trace(city, f"v{len(reqs)}", rng, noise_m=4.0,
                            min_route_edges=5, max_route_edges=max_edges)
        if tr is not None:
            reqs.append({"uuid": tr.uuid, "trace": tr.points,
                         "match_options": {}})
    return reqs


def _bodies(results):
    out = []
    for r in results:
        if isinstance(r, MatchRuns):
            out.append(render_segments_json(r.cols, r.lo, r.hi, r.mode))
        else:
            out.append(json.dumps(r, separators=(",", ":")))
    return out


# one leg of the forced-host-device parity matrix: seeded city + 5
# traces (NOT divisible by any mesh size — filler rows exercised) end
# to end, plus a raw synthetic decode with an all-SKIP filler row; the
# digest covers report bytes AND path bits
_LEG = r"""
import hashlib, json, os
import numpy as np
from reporter_tpu.utils.runtime import ensure_backend
ensure_backend()
import jax
want = int(os.environ["REPORTER_TPU_VIRTUAL_DEVICES"])
assert len(jax.devices()) == want, (len(jax.devices()), want)
from reporter_tpu.matcher import MatchParams, SegmentMatcher
from reporter_tpu.matcher.matcher import MatchRuns, render_segments_json
from reporter_tpu.synth import build_grid_city, generate_trace
city = build_grid_city(rows=8, cols=8, spacing_m=200.0, seed=17)
m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
if want > 1:
    assert m.decode_mesh is not None and m.decode_mesh.devices.size == want
rng = np.random.default_rng(23)
reqs = []
while len(reqs) < 5:
    tr = generate_trace(city, f"v{len(reqs)}", rng, noise_m=4.0,
                        min_route_edges=5, max_route_edges=10)
    if tr is not None:
        reqs.append({"uuid": tr.uuid, "trace": tr.points,
                     "match_options": {}})
res = m.match_many(reqs)
h = hashlib.sha256()
for r in res:
    if isinstance(r, MatchRuns):
        body = render_segments_json(r.cols, r.lo, r.hi, r.mode)
    else:
        body = json.dumps(r, separators=(",", ":"))
    h.update(body.encode())
from reporter_tpu.matcher.hmm import NORMAL, RESTART, SKIP
from reporter_tpu import ops
rng2 = np.random.default_rng(5)
B, T, K = 8, 16, 4
dist = rng2.uniform(0, 30, (B, T, K)).astype(np.float32)
valid = np.ones((B, T, K), bool)
gc = rng2.uniform(5, 40, (B, T - 1)).astype(np.float32)
route = rng2.uniform(5, 80, (B, T - 1, K, K)).astype(np.float32)
case = np.full((B, T), NORMAL, np.int32)
case[:, 0] = RESTART
case[-1, :] = SKIP  # an all-SKIP filler row must decode inertly
paths, _ = ops.decode_batch(dist, valid, route, gc, case,
                            np.float32(4.07), np.float32(3.0))
if want > 1:
    assert len(paths.sharding.device_set) == want
h.update(np.asarray(paths).tobytes())
print("DIGEST:" + h.hexdigest())
"""


def _run_leg(n_devices: int) -> str:
    env = dict(os.environ,
               REPORTER_TPU_PLATFORM="cpu",
               REPORTER_TPU_VIRTUAL_DEVICES=str(n_devices),
               REPORTER_TPU_DECODE="scan",
               REPORTER_TPU_PIPELINE="0",
               REPORTER_TPU_SHARD="1")
    env.pop("REPORTER_TPU_DEVICE_SLICE", None)
    env.pop("REPORTER_TPU_DECODE_SHARD", None)
    proc = subprocess.run([sys.executable, "-c", _LEG],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for line in proc.stdout.splitlines():
        if line.startswith("DIGEST:"):
            return line[len("DIGEST:"):]
    raise AssertionError(f"no digest in leg output: {proc.stdout!r}")


class TestForcedHostDeviceParity:
    """The acceptance matrix: N∈{1,2,8} forced host devices, one
    digest over /report bodies + raw path bits, all equal — the
    sharded scan decode IS the single-device scan decode."""

    def test_bit_identity_across_1_2_8_devices(self):
        digests = {n: _run_leg(n) for n in (1, 2, 8)}
        assert digests[2] == digests[1], digests
        assert digests[8] == digests[1], digests


class TestShardedMatchInProcess:
    """In-process (conftest's virtual 8-device mesh): the serving path
    byte-identity + the fan-out sensors."""

    def test_report_bodies_byte_identical_sharded_vs_single(
            self, city, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = _mixed_reqs(city)  # 5 traces: rows pad 5 -> 8 (filler)
        sharded = _bodies(m.match_many(reqs))
        assert any('"segments":[{' in b for b in sharded)
        monkeypatch.setenv("REPORTER_TPU_DECODE_SHARD", "0")
        ops.reset_sharded_cache()
        single = _bodies(m.match_many(reqs))
        assert sharded == single

    def test_sharded_chunks_counted_and_mesh_in_shape_key(
            self, city, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        profiler.reset()
        before = metrics.default.counter("decode.shard.chunks")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        assert m.decode_mesh is not None
        m.match_many(_mixed_reqs(city))
        assert metrics.default.counter("decode.shard.chunks") > before
        shapes = profiler.snapshot(n_events=0)["shapes"]
        assert shapes and all(s["mesh"] == 8 for s in shapes)

    def test_mesh_change_is_new_shape_not_storm(self, city, monkeypatch):
        """The satellite contract: the same (B, T, K) dispatched on a
        different mesh width is a NEW compile-shape entry — zero
        recompile flags."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        profiler.reset()
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = _mixed_reqs(city)
        m.match_many(reqs)
        monkeypatch.setenv("REPORTER_TPU_DECODE_SHARD", "0")
        ops.reset_sharded_cache()
        m.match_many(reqs)
        shapes = profiler.snapshot(n_events=0)["shapes"]
        meshes = {s["mesh"] for s in shapes}
        assert meshes == {1, 8}
        assert sum(max(0, s["compiles"] - 1) for s in shapes) == 0

    def test_decode_chunk_and_dispatch_depth_scale_with_mesh(
            self, monkeypatch):
        chunk_mesh = _decode_chunk()
        depth_mesh = match_batch_default()
        monkeypatch.setenv("REPORTER_TPU_DECODE_SHARD", "off")
        ops.reset_sharded_cache()
        chunk_one = _decode_chunk()
        assert chunk_mesh == 8 * chunk_one
        assert depth_mesh == max(256, 2 * chunk_mesh)
        # no mesh -> the shipped 256 stands: the 2-chunk depth exists
        # for mesh utilisation, not for fattening single-device
        # batches (tail latency / peak memory)
        assert match_batch_default() == 256

    def test_shard_kill_switches(self, monkeypatch):
        assert ops.decode_mesh_size() == 8
        monkeypatch.setenv("REPORTER_TPU_DECODE_SHARD", "off")
        ops.reset_sharded_cache()
        assert ops.decode_mesh_size() == 1
        assert ops.batch_pad_multiple() is None
        monkeypatch.delenv("REPORTER_TPU_DECODE_SHARD", raising=False)
        monkeypatch.setenv("REPORTER_TPU_SHARD", "0")
        ops.reset_sharded_cache()
        assert ops.decode_mesh_size() == 1

    def test_scan_pad_multiple_is_mesh_size(self, monkeypatch):
        """scan now shards along data (the bit-identity backend), so a
        forced scan backend still pads to the mesh multiple."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        assert ops.batch_pad_multiple() == 8
        monkeypatch.setenv("REPORTER_TPU_DECODE", "pallas")
        assert ops.batch_pad_multiple() is None


class TestDeviceSlice:
    def _slice(self, monkeypatch, spec, n=8):
        from reporter_tpu.parallel import mesh as pmesh
        monkeypatch.setenv(pmesh.ENV_DEVICE_SLICE, spec)
        return pmesh.device_slice(list(range(n)))

    def test_slot_of_procs_blocks(self, monkeypatch):
        assert self._slice(monkeypatch, "0/2") == [0, 1, 2, 3]
        assert self._slice(monkeypatch, "1/2") == [4, 5, 6, 7]
        assert self._slice(monkeypatch, "3/4") == [6, 7]

    def test_more_procs_than_devices_gets_one_each(self, monkeypatch):
        # 8 slots over 4 devices: block math lands slot 5 on device 2,
        # slot 0's empty block falls back to device 0 — every slot
        # always owns exactly one device
        assert self._slice(monkeypatch, "5/8", n=4) == [2]
        assert self._slice(monkeypatch, "0/8", n=4) == [0]

    def test_empty_block_fallback_spreads_evenly(self, monkeypatch):
        # 4 slots over 2 devices must land 2/2, not 3/1: the
        # empty-block fallback uses the proportional index, never
        # slot % n (which piled slots 0 and 2 both onto device 0)
        owned = [self._slice(monkeypatch, f"{s}/4", n=2)[0]
                 for s in range(4)]
        assert owned == [0, 0, 1, 1]

    def test_explicit_range_and_garbage(self, monkeypatch):
        assert self._slice(monkeypatch, "2:4") == [2, 3]
        assert self._slice(monkeypatch, "banana") == list(range(8))
        assert self._slice(monkeypatch, "9/4") == list(range(8))

    def test_sliced_mesh_size(self, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_DEVICE_SLICE", "0/4")
        ops.reset_sharded_cache()
        assert ops.decode_mesh_size() == 2

    def test_prefork_worker_derives_slot_slice(self, monkeypatch):
        import signal
        from reporter_tpu.service import prefork
        # setenv("") so monkeypatch RECORDS both vars (delenv on an
        # absent var records nothing) and worker_main's direct
        # os.environ writes roll back at teardown; "" is falsy, so the
        # worker still derives its slot slice
        monkeypatch.setenv("REPORTER_TPU_DEVICE_SLICE", "")
        monkeypatch.setenv("REPORTER_TPU_WRITER_ID", "")
        old_term = signal.getsignal(signal.SIGTERM)
        old_int = signal.getsignal(signal.SIGINT)
        captured = {}

        class _Stop(Exception):
            pass

        def boom():
            captured["slice"] = os.environ.get(
                "REPORTER_TPU_DEVICE_SLICE")
            raise _Stop()

        try:
            with pytest.raises(_Stop):
                prefork.worker_main(1, boom, "127.0.0.1", 0, procs=2)
        finally:
            signal.signal(signal.SIGTERM, old_term)
            signal.signal(signal.SIGINT, old_int)
        assert captured["slice"] == "1/2"


class TestChunkPlan:
    def test_ladder_default_and_env(self, monkeypatch):
        assert bucket_ladder() == (LENGTH_BUCKETS, None)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "8,32,128@0.5")
        assert bucket_ladder() == ((8, 32, 128), None)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "64@off")
        assert bucket_ladder() == ((64,), None)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "@off")
        assert bucket_ladder() == (LENGTH_BUCKETS, None)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "64,16@0.2")  # bad
        assert bucket_ladder() == (LENGTH_BUCKETS, None)

    def test_plan_ignores_history(self):
        """The plan is a function of the group alone: a recorded, very
        wasteful chunk of the planned T changes nothing."""
        profiler.reset()
        raws = np.array([24, 24, 60, 64], dtype=np.int64)
        group = np.arange(4, dtype=np.int64)
        before = plan_chunks(raws, group, 128, None)
        assert [(T, p.tolist()) for T, p in before] == [(64, [0, 1, 2, 3])]
        profiler.chunk_event(bucket_T=64, K=8, traces=4, rows=4,
                             kept_points=int(0.1 * 4 * 64),
                             raw_points=256)
        after = plan_chunks(raws, group, 128, None)
        assert [(T, p.tolist()) for T, p in after] \
            == [(T, p.tolist()) for T, p in before]
        profiler.reset()

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="the chunk plan lives in the native dispatch")
    def test_plan_t_results_byte_identical(self, city, monkeypatch):
        """The T a chunk decodes at changes shapes, never bytes: the
        SKIP tail is inert, so a trace decoded at its power-of-two class
        yields the same report body as at its ladder bucket (the
        coarse_buckets rung)."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        # a chunk of 8 rows: 8 traces at raw 18 (class 32, bucket 64)
        # decode in one chunk, 8 near-full at raw 60 in another
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", "8")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = _mixed_reqs(city, n=16, seed=31, max_edges=14)
        for r in reqs[:8]:
            r["trace"] = r["trace"][:18]
        for r in reqs[8:]:
            r["trace"] = r["trace"][:60]
        monkeypatch.setattr(batchpad, "_pressure_coarse", True)
        profiler.reset()
        coarse = _bodies(m.match_many(reqs))
        waste_coarse = profiler.padding_waste()
        monkeypatch.setattr(batchpad, "_pressure_coarse", False)
        profiler.reset()
        fine = _bodies(m.match_many(reqs))
        waste_fine = profiler.padding_waste()
        assert fine == coarse
        assert waste_fine < waste_coarse
        profiler.reset()

    #: raw lengths of one params group, whether the coarse_buckets rung
    #: holds, the rows of a chunk, and the chunks planned: [(T, traces)]
    PLANS = {
        # a sparse-fleet micro-batch: classes 16 to 128, one chunk
        "sparse": (np.linspace(12, 110, 15).round(), False, 128,
                   [(128, 15)]),
        # a 1 Hz micro-batch: classes 128 and 256, no split pays
        "1hz": (np.linspace(70, 250, 13).round(), False, 128, [(256, 13)]),
        # a split at 128 would save 1,024 cells, under a chunk's cost
        "1hz-split": ([70] * 8 + [250] * 5, False, 128, [(256, 13)]),
        # the coarse_buckets rung: every class its ladder bucket
        "sparse-coarse": (np.linspace(12, 110, 15).round(), True, 128,
                          [(256, 15)]),
        # one long trace among short ones: one chunk would pad 127 rows
        # of 16 to 1024, far more cells than the chunk it saves
        "outlier": ([1000] + [16] * 127, False, 128,
                    [(16, 127), (1024, 1)]),
        # larger than one chunk: cut in class order, the first run split
        # where the 256 class starts
        "mixed-200": ([12] * 30 + [20] * 20 + [40] * 20 + [200] * 130,
                      False, 128, [(64, 70), (256, 58), (256, 72)]),
        # the four-chip mesh's (or an unpipelined host's) 512-row chunk
        "mesh-512": ([12] * 100 + [40] * 100 + [100] * 312, False, 512,
                     [(128, 512)]),
        "mesh-128": (np.linspace(12, 110, 128).round(), False, 512,
                     [(128, 128)]),
        "mesh-129": (np.linspace(12, 110, 129).round(), False, 512,
                     [(64, 69), (128, 60)]),
        # each split saves more than a chunk's cost: 32|64, then 16|32
        "split-16-32-64": ([10] * 128 + [17] * 128 + [30] * 128
                           + [60] * 128, False, 512,
                           [(16, 128), (32, 256), (64, 128)]),
        # one class: nothing to split
        "full-64": ([60, 61, 62, 64], False, 128, [(64, 4)]),
        # cells count per chunk, as dispatch pads them: one short trace
        # past a full chunk is not split off the 512 it sorts into
        "chunk-aware-513": ([64] * 512 + [16], False, 512,
                            [(64, 512), (64, 1)]),
        # the split-16-32-64 group under the coarse_buckets rung
        "coarse-rung": ([10] * 128 + [17] * 128 + [30] * 128 + [60] * 128,
                        True, 512, [(64, 512)]),
        # a backfill call: 503 windows of 480-1,024 points
        "backfill": (np.linspace(480, 1024, 503).round(), False, 128,
                     [(1024, 128), (1024, 128), (1024, 128), (1024, 119)]),
    }

    @pytest.mark.parametrize("case", sorted(PLANS))
    def test_chunk_plan(self, case, monkeypatch):
        raws, coarse, chunk, want = self.PLANS[case]
        monkeypatch.setattr(batchpad, "_pressure_coarse", coarse)
        raws = np.asarray(raws, dtype=np.int64)
        group = np.arange(len(raws), dtype=np.int64)
        plan = plan_chunks(raws, group, chunk, None)
        assert [(T, len(part)) for T, part in plan] == want
        covered = np.concatenate([part for _, part in plan])
        assert sorted(covered.tolist()) == group.tolist()

    @pytest.mark.parametrize("case", ["sparse", "1hz", "1hz-split",
                                      "mesh-128", "backfill"])
    def test_planned_shape_is_warmed(self, case, monkeypatch):
        """Both warm-ups build every shape the plan forms: the
        benchmark's served warm-up (pow2 rows up to the dispatcher's
        cap, each pow2 T pinned in turn) and ``SegmentMatcher.warm``'s
        (pow2 rows up to a chunk, pow2 T from the ladder's floor to the
        longest trace's bucket)."""
        from reporter_tpu.matcher.batchpad import padded_batch_rows
        monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
        import serve_http
        served = set()

        class Service:  # records the (rows, T) each warm batch forms
            class dispatcher:
                max_batch = 256

            @staticmethod
            def report_many(reqs):
                served.add((padded_batch_rows(len(reqs), None),
                            bucket_ladder()[0][0]))
                return [{}] * len(reqs)

        raws = np.asarray(self.PLANS[case][0], dtype=np.int64)
        top = int(raws.max())
        serve_http.warm_shapes(Service, [{}] * 256, top)
        warmed = {(padded_batch_rows(n, None), T)
                  for n, T in SegmentMatcher._warm_shapes(top, 128)}
        plan = plan_chunks(raws, np.arange(len(raws), dtype=np.int64),
                           128, None)
        for T, part in plan:
            assert (padded_batch_rows(len(part), None), T) in warmed
            assert (padded_batch_rows(len(part), None), T) in served

    @staticmethod
    def _cut(reqs, lengths):
        for r, n in zip(reqs, lengths):
            r["trace"] = r["trace"][:n]
        return reqs

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="the chunk plan lives in the native dispatch")
    @pytest.mark.parametrize("lengths,chunk,chunks", [
        ((12, 12, 40, 40, 60, 60), "", 1),  # three classes, one chunk
        ((60,) * 6, "", 1),                 # one class, one chunk
        ((12, 40, 60, 60) * 4, "8", 2),     # over a chunk of 8 rows
    ])
    def test_chunk_counters(self, city, monkeypatch, lengths, chunk,
                            chunks):
        """``decode.chunks`` counts every chunk planned, and each
        chunk's wide event feeds the lifetime padding waste."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", chunk)
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = self._cut(_mixed_reqs(city, n=len(lengths), seed=31,
                                     max_edges=14), lengths)
        profiler.reset()
        c0 = metrics.default.counter("decode.chunks")
        m.match_many(reqs)
        assert metrics.default.counter("decode.chunks") - c0 == chunks
        assert profiler.padding_waste() is not None
        profiler.reset()

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="the chunk plan lives in the native dispatch")
    def test_plan_chunking_results_byte_identical(self, city, monkeypatch):
        """Cutting a group into more chunks changes shapes, never bytes:
        the same 16 mixed traces as one chunk and as two chunks of 8
        rows give the same report bodies."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = self._cut(_mixed_reqs(city, n=16, seed=31, max_edges=14),
                         (12, 18, 30, 60) * 4)
        c0 = metrics.default.counter("decode.chunks")
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", "8")
        two = _bodies(m.match_many(reqs))
        assert metrics.default.counter("decode.chunks") - c0 == 2
        monkeypatch.delenv("REPORTER_TPU_DECODE_CHUNK")
        one = _bodies(m.match_many(reqs))
        assert metrics.default.counter("decode.chunks") - c0 == 3
        assert one == two
        profiler.reset()

    def test_chunks_per_batch_reader(self, monkeypatch):
        """The benchmark's reader: chunks a dispatcher batch; nothing
        from a program without the counter."""
        import importlib.util
        monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
        from readings import Readings
        name = "decode_chunks_per_batch.closed"
        spec = importlib.util.spec_from_file_location(
            "metric_" + name.replace(".", "_"),
            os.path.join(REPO, "benchmark", "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        got = mod.read(Readings({"decode.chunks": 36, "dispatch.batches": 10},
                                {}, {}, None, "cpu"))
        assert got == pytest.approx(3.6)
        assert mod.read(Readings({"dispatch.batches": 10}, {}, {}, None,
                                 "cpu")) is None


class TestMultichipGate:
    def _art(self, tmp_path, legs, ratios):
        art = {"n_devices": max(l["n_devices"] for l in legs), "rc": 0,
               "ok": True, "skipped": False, "tail": "",
               "legs": legs, "ratios": ratios}
        p = tmp_path / "multichip.json"
        p.write_text(json.dumps(art))
        return str(p)

    def test_gate_rejects_devices_seen_mismatch(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import perf_gate
        path = self._art(tmp_path, [
            {"n_devices": 1, "rc": 0, "traces_per_sec": 100.0,
             "devices_seen": 1},
            {"n_devices": 4, "rc": 0, "traces_per_sec": 90.0,
             "devices_seen": 1},  # the r06 failure mode
        ], {"4": 0.9})
        passed, verdict = perf_gate.gate_multichip(path, 0.5)
        assert not passed
        assert any(f.get("devices_seen") == 1 and f.get("n_devices") == 4
                   for f in verdict["failures"])

    def test_gate_passes_matching_legs(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import perf_gate
        path = self._art(tmp_path, [
            {"n_devices": 1, "rc": 0, "traces_per_sec": 100.0,
             "devices_seen": 1},
            {"n_devices": 4, "rc": 0, "traces_per_sec": 90.0,
             "devices_seen": 4},
        ], {"4": 0.9})
        passed, verdict = perf_gate.gate_multichip(path, 0.5)
        assert passed, verdict


class TestLedgerLegacyScope:
    def test_liveness_only_artifacts_are_legacy(self):
        from reporter_tpu.obs import ledger
        e = ledger._multichip_entry("MULTICHIP_r03.json",
                                    {"n_devices": 8, "rc": 0, "ok": True})
        assert e["scope"] == "legacy"
        assert e["vs_baseline"] is None

    def test_r06_style_mismatched_legs_are_legacy(self):
        from reporter_tpu.obs import ledger
        e = ledger._multichip_entry("MULTICHIP_r06.json", {
            "n_devices": 2, "ok": True, "ratios": {"2": 0.7},
            "legs": [{"n_devices": 1, "devices_seen": 1,
                      "traces_per_sec": 10.0},
                     {"n_devices": 2, "devices_seen": 1,
                      "traces_per_sec": 7.0}]})
        assert e["scope"] == "legacy"
        assert e["vs_baseline"] is None

    def test_measured_artifacts_stay_full(self):
        from reporter_tpu.obs import ledger
        e = ledger._multichip_entry("MULTICHIP_r07.json", {
            "n_devices": 2, "ok": True, "ratios": {"2": 1.1},
            "legs": [{"n_devices": 1, "devices_seen": 1,
                      "traces_per_sec": 10.0},
                     {"n_devices": 2, "devices_seen": 2,
                      "traces_per_sec": 11.0}]})
        assert e["scope"] == "full"
        assert e["vs_baseline"] == 1.1

    def test_committed_legacy_artifacts_out_of_median_pools(self):
        from reporter_tpu.obs import ledger
        entries = ledger.seed_entries(REPO)
        legacy = [e for e in entries if e["kind"] == "multichip"
                  and e["scope"] == "legacy"]
        assert {e["source"] for e in legacy} >= {
            f"MULTICHIP_r0{i}.json" for i in range(1, 6)}
        for e in legacy:
            assert e["vs_baseline"] is None  # can never enter a median
