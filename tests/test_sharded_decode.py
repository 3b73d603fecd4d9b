"""ISSUE 13: device-mesh decode scale-out + adaptive bucketing.

The contract under test is BIT-IDENTITY: the 1-D ``("data",)`` decode
mesh carries no collective, so the sharded scan decode must equal the
single-device scan backend bit for bit — same Viterbi paths, same
/report bytes — at every forced host-device count. Subprocess legs pin
it at N∈{1,2,8} (the device count is fixed at backend init, so each N
is its own interpreter); in-process tests cover the conftest 8-device
mesh, the rows-not-divisible-by-mesh chunk, all-SKIP filler rows, the
adaptive bucket splitter, and the new knobs/gates.
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
from reporter_tpu.matcher.batchpad import (DEFAULT_SPLIT_WASTE,
                                           LENGTH_BUCKETS, bucket_ladder)
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


class TestAdaptiveBucketing:
    def test_ladder_default_and_env(self, monkeypatch):
        assert bucket_ladder() == (LENGTH_BUCKETS, DEFAULT_SPLIT_WASTE)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "8,32,128@0.5")
        assert bucket_ladder() == ((8, 32, 128), 0.5)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "@off")
        assert bucket_ladder() == (LENGTH_BUCKETS, 1.0)
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "64,16@0.2")  # bad
        assert bucket_ladder() == (LENGTH_BUCKETS, DEFAULT_SPLIT_WASTE)

    def test_split_plan_projection(self):
        """A mixed 64-bucket group whose raw lengths project waste past
        the threshold splits into pow2 sub-buckets covering exactly the
        original indices; the chunk plan of a group larger than one
        chunk keeps the split and counts it."""
        profiler.reset()
        group = np.arange(8, dtype=np.int64)
        raws = np.array([10, 10, 17, 17, 30, 30, 60, 60], dtype=np.int64)
        plan = SegmentMatcher._split_bucket(64, group, raws)
        assert [t for t, _ in plan] == [16, 32, 64]
        covered = np.concatenate([idx for _, idx in plan])
        assert sorted(covered.tolist()) == group.tolist()
        before = metrics.default.counter("decode.bucket.split")
        chunks = SegmentMatcher._plan_chunks(group, raws, None, 4)
        assert [(t, p.tolist(), c) for t, p, c in chunks] == [
            (16, [0, 1], False), (32, [2, 3, 4, 5], False),
            (64, [6, 7], False)]
        assert metrics.default.counter("decode.bucket.split") == before + 1

    @staticmethod
    def _is_noop(plan, T, group):
        return len(plan) == 1 and plan[0][0] == T and plan[0][1] is group

    def test_split_plan_skips_full_buckets(self):
        profiler.reset()
        group = np.arange(4, dtype=np.int64)
        raws = np.array([60, 61, 62, 64], dtype=np.int64)
        assert self._is_noop(
            SegmentMatcher._split_bucket(64, group, raws), 64, group)

    def test_split_plan_consults_recorded_waste(self):
        """The ISSUE wording, pinned: once the PR 8 wide events have
        RECORDED high waste for a shape, the dispatcher splits even a
        group whose raw lengths project full buckets (kept < raw is
        exactly what the projection can't see)."""
        profiler.reset()
        group = np.arange(4, dtype=np.int64)
        raws = np.array([60, 61, 62, 64], dtype=np.int64)
        # record one very wasteful 64-bucket chunk (occupancy 0.1)
        # a mildly-mixed group that PROJECTS under the threshold
        # (1 - 204/256 = 0.20): no split before any chunk is measured
        raws2 = np.array([24, 24, 60, 64], dtype=np.int64)
        assert self._is_noop(
            SegmentMatcher._split_bucket(64, group, raws2), 64, group)
        # record one very wasteful 64-bucket chunk (occupancy 0.1) —
        # the same group now splits on the measured record alone
        profiler.chunk_event(bucket_T=64, K=8, traces=4, rows=4,
                             kept_points=int(0.1 * 4 * 64),
                             raw_points=256)
        plan2 = SegmentMatcher._split_bucket(64, group, raws2)
        assert [t for t, _ in plan2] == [32, 64]
        # full-length raws can't split no matter what the record says
        assert self._is_noop(
            SegmentMatcher._split_bucket(64, group, raws), 64, group)
        profiler.reset()

    def test_split_projection_is_chunk_aware(self):
        """A group one trace past the chunk boundary must not read the
        whole-group pow2 row padding as reclaimable waste: cells are
        accounted per CHUNK, exactly as dispatch pads them, so a
        near-perfectly-packed 513-trace group stays unsplit."""
        profiler.reset()
        group = np.arange(513, dtype=np.int64)
        raws = np.full(513, 64, dtype=np.int64)
        raws[-1] = 16
        plan = SegmentMatcher._split_bucket(64, group, raws, None, 512)
        assert self._is_noop(plan, 64, group)

    def test_split_disabled_by_off_threshold(self, monkeypatch):
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "@off")
        group = np.arange(8, dtype=np.int64)
        raws = np.array([10] * 8, dtype=np.int64)
        assert self._is_noop(
            SegmentMatcher._split_bucket(64, group, raws), 64, group)

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="splitter lives in the native dispatch")
    def test_split_results_byte_identical(self, city, monkeypatch):
        """Splitting changes shapes, never bytes: the SKIP tail is
        inert, so a trace decoded at its pow2 sub-bucket yields the
        same report body as at the full ladder bucket."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        # a chunk of 8 rows: the 16-trace group is larger than one
        # chunk, so it takes the per-bucket plan and may split
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", "8")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        # mixed lengths in ONE 64-bucket: 8 traces at raw 18 (pads to
        # 64 fixed, 32 split) + 8 near-full at raw 60 — each sub-batch
        # is a whole mesh multiple, so the split's row re-padding
        # can't eat the reclaimed tail
        reqs = _mixed_reqs(city, n=16, seed=31, max_edges=14)
        for r in reqs[:8]:
            r["trace"] = r["trace"][:18]
        for r in reqs[8:]:
            r["trace"] = r["trace"][:60]
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "@off")
        profiler.reset()
        fixed = _bodies(m.match_many(reqs))
        waste_fixed = profiler.padding_waste()
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", "@0.2")
        profiler.reset()
        before = metrics.default.counter("decode.bucket.split")
        adaptive = _bodies(m.match_many(reqs))
        waste_adaptive = profiler.padding_waste()
        assert fixed == adaptive
        assert metrics.default.counter("decode.bucket.split") > before
        assert waste_adaptive < waste_fixed

    #: raw lengths of one params group, the REPORTER_TPU_BUCKETS spec,
    #: the rows of a chunk, and the chunks planned: [(T, traces)], then
    #: whether the group was merged into one chunk
    PLANS = {
        # a sparse-fleet micro-batch: ladder buckets 16, 64 and 256
        "sparse": (np.linspace(12, 110, 15).round(), "", 128,
                   [(128, 15)], True),
        # a 1 Hz micro-batch: one 256 bucket no split pays for
        "1hz": (np.linspace(70, 250, 13).round(), "", 128, [(256, 13)],
                False),
        # one 256 bucket the splitter would cut at 128
        "1hz-split": ([70] * 8 + [250] * 5, "", 128, [(256, 13)], True),
        # splitting off (the pressure ladder's coarse rung): merged at
        # the longest trace's ladder bucket, no new shape
        "sparse-coarse": (np.linspace(12, 110, 15).round(), "@off", 128,
                          [(256, 15)], True),
        # one long trace among short ones: merging would pad 127 rows
        # of 16 to 1024, far more cells than the chunk it saves
        "outlier": ([1000] + [16] * 127, "", 128,
                    [(16, 127), (1024, 1)], False),
        # larger than one chunk: the per-bucket plan, split and cut
        "mixed-200": ([12] * 30 + [20] * 20 + [40] * 20 + [200] * 130, "",
                      128,
                      [(16, 30), (32, 20), (64, 20), (256, 128), (256, 2)],
                      False),
        # the four-chip mesh's (or an unpipelined host's) 512-row chunk:
        # the merge still takes at most 128 traces
        "mesh-512": ([12] * 100 + [40] * 100 + [100] * 312, "", 512,
                     [(16, 100), (64, 100), (128, 312)], False),
        "mesh-128": (np.linspace(12, 110, 128).round(), "", 512,
                     [(128, 128)], True),
        "mesh-129": (np.linspace(12, 110, 129).round(), "", 512,
                     [(16, 6), (64, 63), (128, 60)], False),
    }

    @pytest.mark.parametrize("case", sorted(PLANS))
    def test_chunk_plan(self, case, monkeypatch):
        raws, spec, chunk, want, merged = self.PLANS[case]
        monkeypatch.setenv("REPORTER_TPU_BUCKETS", spec)
        profiler.reset()
        raws = np.asarray(raws, dtype=np.int64)
        group = np.arange(len(raws), dtype=np.int64)
        before = metrics.default.counter("decode.bucket.coalesced")
        plan = SegmentMatcher._plan_chunks(group, raws, None, chunk)
        assert [(T, len(part)) for T, part, _ in plan] == want
        assert all(c == merged for _, _, c in plan)
        covered = np.concatenate([part for _, part, _ in plan])
        assert sorted(covered.tolist()) == group.tolist()
        assert metrics.default.counter("decode.bucket.coalesced") \
            == before + merged

    @pytest.mark.parametrize("case", ["sparse", "1hz", "1hz-split",
                                      "mesh-128"])
    def test_coalesced_shape_is_warmed(self, case, monkeypatch):
        """The benchmark's warm-up builds every shape a merge forms:
        pow2 rows up to the dispatcher's cap, pow2 T from the ladder's
        floor to the longest trace's bucket."""
        from reporter_tpu.matcher.batchpad import padded_batch_rows
        monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
        import serve_http
        warmed = set()

        class Service:  # records the (rows, T) each warm batch forms
            class dispatcher:
                max_batch = 256

            @staticmethod
            def report_many(reqs):
                warmed.add((padded_batch_rows(len(reqs), None),
                            bucket_ladder()[0][0]))
                return [{}] * len(reqs)

        serve_http.warm_shapes(Service, [{}] * 256, 256)
        raws, _spec, _chunk, _want, _merged = self.PLANS[case]
        raws = np.asarray(raws, dtype=np.int64)
        profiler.reset()
        plan = SegmentMatcher._plan_chunks(
            np.arange(len(raws), dtype=np.int64), raws, None, 128)
        for T, part, _ in plan:
            assert (padded_batch_rows(len(part), None), T) in warmed

    @staticmethod
    def _cut(reqs, lengths):
        for r, n in zip(reqs, lengths):
            r["trace"] = r["trace"][:n]
        return reqs

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="the chunk plan lives in the native dispatch")
    @pytest.mark.parametrize("lengths,chunk,chunks,merged", [
        ((12, 12, 40, 40, 60, 60), "", 1, 1),  # three buckets, merged
        ((60,) * 6, "", 1, 0),                 # one bucket, one chunk
        ((12, 40, 60, 60) * 4, "8", 3, 0),     # over a chunk of 8 rows
    ])
    def test_chunk_counters(self, city, monkeypatch, lengths, chunk,
                            chunks, merged):
        """``decode.chunks`` counts every chunk planned,
        ``decode.bucket.coalesced`` every merge; a merged chunk's
        padding stays out of the per-T waste the splitter reads."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", chunk)
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = self._cut(_mixed_reqs(city, n=len(lengths), seed=31,
                                     max_edges=14), lengths)
        profiler.reset()
        c0 = metrics.default.counter("decode.chunks")
        m0 = metrics.default.counter("decode.bucket.coalesced")
        m.match_many(reqs)
        assert metrics.default.counter("decode.chunks") - c0 == chunks
        assert metrics.default.counter("decode.bucket.coalesced") - m0 \
            == merged
        assert profiler.padding_waste() is not None
        recorded = [profiler.bucket_waste(T) for T in (16, 32, 64)]
        assert (recorded == [None] * 3) == bool(merged)
        profiler.reset()

    @pytest.mark.skipif(
        not __import__("reporter_tpu.native", fromlist=["available"])
        .available(), reason="the chunk plan lives in the native dispatch")
    def test_coalesced_results_byte_identical(self, city, monkeypatch):
        """Merging changes shapes, never bytes: the same 16 mixed traces
        as one merged chunk and as the per-bucket plan's chunks (a chunk
        of 8 rows) give the same report bodies."""
        monkeypatch.setenv("REPORTER_TPU_DECODE", "scan")
        m = SegmentMatcher(net=city, params=MatchParams(max_candidates=6))
        reqs = self._cut(_mixed_reqs(city, n=16, seed=31, max_edges=14),
                         (12, 18, 30, 60) * 4)
        before = metrics.default.counter("decode.bucket.coalesced")
        monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", "8")
        per_bucket = _bodies(m.match_many(reqs))
        assert metrics.default.counter("decode.bucket.coalesced") == before
        monkeypatch.delenv("REPORTER_TPU_DECODE_CHUNK")
        merged = _bodies(m.match_many(reqs))
        assert metrics.default.counter("decode.bucket.coalesced") \
            == before + 1
        assert merged == per_bucket
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

    def test_padding_waste_gate_skip_and_fail(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import perf_gate
        # an EXPLICIT native-less skip passes with a note
        ok, v = perf_gate.gate_padding_waste(
            {"source": "a", "bucketing": {"skipped": "no native"}}, 0.1)
        assert ok and "skipped" in v["note"]
        # a silently missing block still fails loudly
        ok, _v = perf_gate.gate_padding_waste({"source": "a"}, 0.1)
        assert not ok
        # and the ceiling binds
        ok, _v = perf_gate.gate_padding_waste(
            {"source": "a", "bucketing": {"adaptive_waste": 0.2,
                                          "fixed_waste": 0.5}}, 0.1)
        assert not ok


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
