#!/usr/bin/env python
"""Benchmark: batched TPU map-matching throughput vs the reference's
one-trace-at-a-time single-process architecture.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "traces/sec", "vs_baseline": N,
   "stages": {...}, "report_writers": {...}, "baseline": {...},
   "device": {...}, "pallas": {...}}

Method: build a synthetic city, synthesise noisy GPS traces, then time
two END-TO-END legs over the same traces (steady state: route caches
warm, shapes compiled — a long-running city service):

  baseline leg — the reference's architecture (reference:
  py/reporter_service.py:240, Batch.java:66-68 — one C++ Meili call per
  trace on one CPU thread): single-threaded host prep + the pure-numpy
  single-trace Viterbi (matcher/cpu_ref.py) + segment assembly +
  report(), one trace at a time, no accelerator; best-of-N over >=100
  traces so the denominator is not a single noisy pass.

  batched leg  — this framework's architecture: SegmentMatcher.match_many
  (ONE native prep call per chunk — C++ candidates/jitter-filter/route
  matrices straight into padded tensors — the platform-default batched
  Viterbi (assoc on accelerators/meshes, scan on a lone CPU device;
  ops.decode_backend), async d2h, ONE native assembly call per batch)
  + report().

``vs_baseline`` is batched/baseline throughput — the architectural
speedup toward BASELINE.md's >=50x-over-single-process-Meili north star,
with the baseline an honest single-process CPU stand-in, not a batch=1
accelerator call.

The artifact is self-diagnosing: ``stages`` carries per-stage seconds of
the best batched run (prep / decode dispatch / decode wait / assemble,
from the matcher's metrics timers, plus report), ``baseline`` the
denominator's scope, ``device`` the platform, kind and count it ran on,
and ``pallas`` a second
decode-backend leg (REPORTER_TPU_DECODE=pallas) recorded on TPU runs so
kernel claims trace to a committed artifact.

Env knobs: BENCH_TRACES (default 512), BENCH_BASELINE_TRACES (default
128), BENCH_T (bucket, default 64), BENCH_K (default 8), BENCH_REPEATS
(default 5), BENCH_BASELINE_REPEATS (default 3), BENCH_PALLAS
(default: auto — on when the platform is tpu), BENCH_PROFILE (a
directory: record one jax.profiler device trace of a batched pass).

One argv escape hatch: ``python bench.py --feed-fanout N [...]`` runs
the freshness tier's change-feed fan-out leg (tools/
feed_fanout_bench.py — N concurrent /feed subscribers over a pre-fork
fleet) instead of the matcher legs.
"""
import json
import os
import sys
import time

import numpy as np


def build_inputs(n_traces, T_bucket, K):
    from reporter_tpu.core.tracebatch import TraceBatch
    from reporter_tpu.matcher import MatchParams, SegmentMatcher
    from reporter_tpu.synth import build_grid_city, generate_trace

    city = build_grid_city(rows=20, cols=20, spacing_m=200.0, seed=42)
    params = MatchParams(max_candidates=K)
    matcher = SegmentMatcher(net=city, params=params)
    rng = np.random.default_rng(7)
    reqs = []
    # routes long enough to fill the bucket at ~1 point/sec, then sliced
    min_edges = max(4, T_bucket // 12)
    attempts = 0
    while len(reqs) < n_traces:
        attempts += 1
        if attempts > 50 * n_traces:
            raise RuntimeError(f"could not build T={T_bucket} traces")
        tr = generate_trace(city, f"veh-{len(reqs)}", rng, noise_m=4.0,
                            min_route_edges=min_edges, max_route_edges=60)
        if tr is None or len(tr.points) < T_bucket // 2:
            continue
        points = tr.points[:T_bucket]
        # prepared only to check the trace fills the bucket exactly
        if matcher.prepare(points).T != T_bucket:
            continue
        req = tr.request_json()
        req["trace"] = points
        req["match_options"] = {"mode": "auto",
                                "report_levels": [0, 1, 2],
                                "transition_levels": [0, 1, 2]}
        reqs.append(req)
    # columnar TraceBatch with ONE shared match_options — what a real
    # ingestion edge (service/streaming/pipeline) hands the matcher; the
    # batched leg measures the zero-dict hot path the service actually
    # runs, the baseline leg keeps the reference's per-trace dicts
    tb = TraceBatch.from_requests(reqs)
    tb.options = reqs[0]["match_options"]
    return city, matcher, params, reqs, tb


def _time_batched_leg(matcher, tb, reqs, make_report, repeats):
    """Best-of-N end-to-end timing of match_many + report over the
    columnar batch ``tb``; returns (best_seconds, stage breakdown of the
    best run). ``reqs`` supplies the request dicts report() reads."""
    from reporter_tpu.matcher import pipeline_enabled
    from reporter_tpu.utils import metrics

    best, best_stages = float("inf"), {}
    for _ in range(repeats):
        metrics.default.reset()
        t0 = time.perf_counter()
        matches = matcher.match_many(tb)
        t_match = time.perf_counter()
        for req, match in zip(reqs, matches):
            make_report(match, req, 15, {0, 1, 2}, {0, 1, 2})
        elapsed = time.perf_counter() - t0
        if elapsed < best:
            best = elapsed
            snap = metrics.snapshot()
            timers = snap["timers"]
            best_stages = {
                name.split(".", 1)[1]: timers[name]["total_s"]
                for name in ("matcher.prep", "matcher.decode_dispatch",
                             "matcher.decode_wait", "matcher.assemble")
                if name in timers}
            # native prep phase split (REPORTER_TPU_PREP_TIMINGS
            # attribution, now always exported through utils.metrics):
            # candidates = wall of the batch-sorted kernel, select/routes
            # are worker-thread-summed — where prep time went, committed
            # in the artifact instead of needing a rerun
            counters = snap["counters"]
            for phase in ("candidates", "select", "routes"):
                ns = counters.get(f"prep.phase.{phase}_ns")
                if ns:
                    best_stages[f"prep_{phase}"] = round(ns / 1e9, 6)
            best_stages["report"] = round(elapsed - (t_match - t0), 6)
            best_stages["total"] = round(elapsed, 6)
            # serialisation's share of the batch wall — the wire-path
            # health number (ISSUE 11: the native writer's target is
            # <=0.15 serialized, from ~0.27 with the Python columnar
            # writer in BENCH_DEV_r06)
            best_stages["report_share"] = round(
                best_stages["report"] / elapsed, 4)
            # prep's share of the batch wall — the host-pipeline health
            # number (BENCH_r05: 62%; the columnar pipeline's target is
            # <35%). Under the device lanes prep overlaps decode, so
            # stage seconds can sum past the wall total; set
            # REPORTER_TPU_PIPELINE=0 for a serialized breakdown.
            best_stages["prep_share"] = round(
                best_stages.get("prep", 0.0) / elapsed, 4)
            best_stages["pipelined"] = pipeline_enabled()
    return best, best_stages


def _time_report_writers(matches, reqs, repeats=3):
    """The serialisation stage in isolation, one leg per wire backend
    over the SAME matches: the native C writer (bytes straight from run
    columns in one GIL-released call), the Python columnar writer (the
    fallback backend / parity oracle), and the legacy per-run-dict +
    json.dumps path the pre-PR-4 service ran. Ratios between legs are
    box-drift-proof (same process, same matches); the native leg is
    None when the toolchain is unavailable."""
    from reporter_tpu import native
    from reporter_tpu.service import wire
    from reporter_tpu.service.report import (_report_json_py, report,
                                             report_wire)

    mm_runs = [(m, r) for m, r in zip(matches, reqs)
               if not isinstance(m, dict)]
    if not mm_runs:
        return None

    def _leg(fn):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for match, req in mm_runs:
                fn(match, req, 15, {0, 1, 2}, {0, 1, 2})
            best = min(best, time.perf_counter() - t0)
        return best

    out = {"n_traces": len(mm_runs)}
    python_s = _leg(_report_json_py)
    out["python_s"] = round(python_s, 6)
    # legacy dict path: dicts pre-materialised outside the timed loop —
    # the pre-PR-4 service got them free from assembly, so charging
    # materialisation here would overstate the win
    plain = [({"segments": [dict(s) for s in m["segments"]],
               "mode": m["mode"]}, r) for m, r in mm_runs]

    def _dict_leg(match, req, thr, rep, trans):
        return json.dumps(report(match, req, thr, rep, trans),
                          separators=(",", ":"))

    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for match, req in plain:
            _dict_leg(match, req, 15, {0, 1, 2}, {0, 1, 2})
        best = min(best, time.perf_counter() - t0)
    out["dict_s"] = round(best, 6)
    out["dict_vs_python"] = round(best / python_s, 3)
    if native.available() and wire.use_native():
        def _native_leg():
            best = float("inf")
            for _ in range(repeats):
                # drop the chunk memos so EVERY repeat pays the whole-
                # chunk C emission plus its slice lookups — without
                # this, repeats 2+ time pure dict hits and the
                # committed native_vs_python ratio would overstate the
                # writer (the serving path builds the memo once per
                # chunk lifetime, which one repeat models exactly)
                for match, _req in mm_runs:
                    match.cols.arrays.pop("_wire_chunk", None)
                t0 = time.perf_counter()
                for match, req in mm_runs:
                    report_wire(match, req, 15, {0, 1, 2}, {0, 1, 2})
                best = min(best, time.perf_counter() - t0)
            return best

        native_s = _native_leg()
        out["native_s"] = round(native_s, 6)
        out["native_vs_python"] = round(native_s / python_s, 3)
    else:
        out["native_s"] = None
        out["native_vs_python"] = None
    return out


def _query_leg(n_segments: int = 256, repeats: int = 3):
    """The serving-tier batched-query pair (ISSUE 14): ONE
    ``query_many(256)`` sweep vs 256 single ``query_segment`` calls
    over the same synthetic store — 8 partitions x 4 live deltas, every
    segment with histogram cells and transitions (the pre-compaction
    steady state a dashboard hits). Answers are asserted EQUAL before
    timing (the speedup must never be a different answer), and the
    best-of-N ratio is gated by ``perf_gate --min-query-ratio``."""
    import shutil
    import tempfile

    from reporter_tpu.core.osmlr import make_segment_id
    from reporter_tpu.datastore import (
        LocalDatastore,
        ObservationBatch,
        query_many,
        query_segment,
    )

    tmp = tempfile.mkdtemp(prefix="bench_query_")
    try:
        ds = LocalDatastore(tmp)
        rng = np.random.default_rng(7)
        tiles = [1000 + i for i in range(8)]
        seg_ids = [make_segment_id(2, tiles[i % 8], i)
                   for i in range(n_segments)]
        seg_arr = np.array(seg_ids, dtype=np.int64)
        for d in range(4):
            n_obs = n_segments * 8
            dur = rng.uniform(5, 30, n_obs)
            obs = ObservationBatch(
                segment_id=rng.choice(seg_arr, size=n_obs),
                next_id=rng.choice(seg_arr, size=n_obs),
                duration_s=dur,
                count=np.ones(n_obs, dtype=np.int64),
                length_m=(dur * rng.uniform(3, 20, n_obs))
                .astype(np.int64) + 1,
                queue_m=np.zeros(n_obs, dtype=np.int64),
                min_ts=rng.integers(1500000000, 1500600000, n_obs),
                max_ts=rng.integers(1500600000, 1500700000, n_obs))
            ds.ingest(obs, ingest_key=f"bench-{d}")

        many = query_many(ds, seg_ids)  # warm handles + assert parity
        singles = [query_segment(ds, s) for s in seg_ids]
        if many != singles:
            raise RuntimeError("query_many answers differ from single "
                               "queries — parity broken, ratio void")
        best_single = best_many = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for s in seg_ids:
                query_segment(ds, s)
            best_single = min(best_single, time.perf_counter() - t0)
        for _ in range(repeats):
            t0 = time.perf_counter()
            query_many(ds, seg_ids)
            best_many = min(best_many, time.perf_counter() - t0)
        return {
            "n_segments": n_segments,
            "partitions": 8,
            "live_deltas_per_partition": 4,
            "single_s": round(best_single, 6),
            "many_s": round(best_many, 6),
            "batch_ratio": round(best_single / best_many, 2),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _routes_leg(city, matcher, params, reqs, n_chunk: int = 64,
                repeats: int = 3):
    """The route-kernel triple (ISSUE 16): the same chunk's candidate
    pairs costed three ways — the chunk-batched device relax
    (graph/route_device.py, its serving shape: ONE fill per chunk), the
    per-trace host Dijkstra (graph/route.py, warm RouteCache) and the
    per-trace native memo (rt_route_matrices, warm memo). BEFORE any
    timing, the serving paths (batch prep, per-trace native, device
    fill) must agree byte-identical — the speedup must never be a
    different answer. The numpy reference accumulates in float64 and
    casts on store, so it is held to the seed's route tolerance
    (rtol=1e-5, atol=1e-3) instead of bytes. Best-of-N wall per leg;
    ``device_vs_native`` is the route-stage speedup the pipelined
    prep_share should reflect when REPORTER_TPU_ROUTE_DEVICE is on."""
    if matcher.runtime is None:
        return {"skipped": "no native runtime: the native prep tensors "
                "are the shared pair workload"}
    from reporter_tpu.graph.route import RouteCache, candidate_route_matrices
    from reporter_tpu.graph.route_device import DeviceRouteKernel
    from reporter_tpu.graph.spatial import CandidateSet
    from reporter_tpu.matcher.batchpad import prepare_batch

    kern = DeviceRouteKernel(city)
    sub = [r["trace"] for r in reqs[:n_chunk]]
    T = matcher.prepare(sub[0]).T
    host = prepare_batch(matcher.runtime, sub, params, T, n_threads=0)
    prep = dict(host.prep)
    B = len(sub)

    def _trace_cands(b):
        nk = int(prep["num_kept"][b])
        edge = prep["edge_ids"][b, :nk]
        off = prep["offset_m"][b, :nk]
        z = np.zeros_like(off)
        cands = CandidateSet(edge_ids=edge, dist_m=prep["dist_m"][b, :nk],
                             offset_m=off, proj_x=z, proj_y=z)
        gc = prep["gc_m"][b, :max(nk - 1, 0)]
        dt = prep["dt"][b, :max(nk - 1, 0)] \
            if params.max_route_time_factor > 0 and nk > 1 else None
        return nk, cands, gc, dt

    kw = dict(max_route_distance_factor=params.max_route_distance_factor,
              backward_tolerance_m=params.backward_tolerance_m,
              max_route_time_factor=params.max_route_time_factor,
              min_time_bound_s=params.min_time_bound_s,
              turn_penalty_factor=params.turn_penalty_factor)
    cache = RouteCache(city)

    # -- parity BEFORE timing: all three paths, identical pairs ----------
    n_pairs = 0
    for b in range(B):
        nk, cands, gc, dt = _trace_cands(b)
        if nk < 2:
            continue
        oracle = prep["route_m"][b, :nk - 1]
        nat = matcher.runtime.route_matrices(cands, gc, dt=dt, **kw)
        np_route = candidate_route_matrices(city, cands, gc, cache=cache,
                                            dt=dt, **kw)
        if not np.array_equal(oracle, nat):
            raise RuntimeError(f"native route paths disagree on trace {b} "
                               "— parity broken, timings void")
        if not np.allclose(oracle, np_route, rtol=1e-5, atol=1e-3):
            raise RuntimeError(f"numpy route reference disagrees on trace "
                               f"{b} — parity broken, timings void")
        n_pairs += int((cands.edge_ids[:-1] != -1).sum()) \
            * cands.edge_ids.shape[1]
    dev = dict(prep)
    dev["route_m"] = prep["route_m"].copy()
    dev["max_finite"] = prep["max_finite"].copy()
    kern.fill_prep(dev, params)  # also warms the jit cache
    if not np.array_equal(dev["route_m"], prep["route_m"]):
        raise RuntimeError("device route tensor differs from the host "
                           "oracle — parity broken, timings void")

    # -- timed legs over the identical, parity-proven workload -----------
    best_dev = best_host = best_nat = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        kern.fill_prep(dev, params)
        best_dev = min(best_dev, time.perf_counter() - t0)
    for _ in range(repeats):
        t0 = time.perf_counter()
        for b in range(B):
            nk, cands, gc, dt = _trace_cands(b)
            if nk >= 2:
                candidate_route_matrices(city, cands, gc, cache=cache,
                                         dt=dt, **kw)
        best_host = min(best_host, time.perf_counter() - t0)
    for _ in range(repeats):
        t0 = time.perf_counter()
        for b in range(B):
            nk, cands, gc, dt = _trace_cands(b)
            if nk >= 2:
                matcher.runtime.route_matrices(cands, gc, dt=dt, **kw)
        best_nat = min(best_nat, time.perf_counter() - t0)
    return {
        "n_traces": B,
        "T": int(T),
        "n_pairs": n_pairs,
        "parity": "byte-identical",
        "device_s": round(best_dev, 6),
        "host_s": round(best_host, 6),
        "native_s": round(best_nat, 6),
        "device_vs_host": round(best_host / best_dev, 2),
        "device_vs_native": round(best_nat / best_dev, 2),
    }


def main():
    n_traces = int(os.environ.get("BENCH_TRACES", 512))
    n_base = int(os.environ.get("BENCH_BASELINE_TRACES", 128))
    T_bucket = int(os.environ.get("BENCH_T", 64))
    K = int(os.environ.get("BENCH_K", 8))
    repeats = int(os.environ.get("BENCH_REPEATS", 5))
    base_repeats = int(os.environ.get("BENCH_BASELINE_REPEATS", 3))

    # REPORTER_TPU_PLATFORM=cpu|tpu, unset = JAX's default; the artifact
    # names the device it ran on
    from reporter_tpu.utils import runtime as rt
    rt.ensure_backend()
    pipeline_unset = not os.environ.get("REPORTER_TPU_PIPELINE", "").strip()

    import jax

    from reporter_tpu.matcher.assemble import assemble_segments
    from reporter_tpu.matcher.cpu_ref import viterbi_decode_numpy
    from reporter_tpu.ops import decode_backend
    # each leg measures its own architecture end-to-end through the
    # wire: the batched leg serialises via report_wire — the serving
    # path's entry point (native C writer emitting response bytes in
    # one GIL-released call when armed, Python columnar writer
    # otherwise) — while the baseline leg keeps report_json, which for
    # its plain-dict matches IS the reference-shaped dict + json.dumps
    from reporter_tpu.service.report import report_json as make_report
    from reporter_tpu.service.report import report_wire

    platform = jax.devices()[0].platform

    # the chunked overlap path is the architecture being measured: the
    # threaded lanes are proven safe (TestDevicePipeline pins identical
    # results), so the batched leg always exercises them unless the
    # operator explicitly said otherwise — the headline then reports
    # pipelined: true with prep overlapping decode/assemble
    if pipeline_unset:
        os.environ["REPORTER_TPU_PIPELINE"] = "1"

    # the batched leg runs with the device route kernel ON by default
    # (BENCH_ROUTE_DEVICE=0 opts out): the committed artifact measures
    # the chunk-batched relax as the serving route path, with the host
    # Dijkstra held to byte-parity by the routes leg below. An explicit
    # REPORTER_TPU_ROUTE_DEVICE in the environment wins.
    if os.environ.get("BENCH_ROUTE_DEVICE", "1") not in ("0", "off",
                                                         "false"):
        os.environ.setdefault("REPORTER_TPU_ROUTE_DEVICE", "1")

    city, matcher, params, reqs, tb = build_inputs(n_traces, T_bucket, K)
    sigma = np.float32(params.effective_sigma)
    beta = np.float32(params.beta)

    # -- baseline leg: the reference architecture, one trace at a time ----
    # single-threaded prep + numpy Viterbi + assembly + report on the CPU;
    # re-prep included so both legs measure the same end-to-end scope
    # (route caches are warm in both — steady state); best-of-N so the
    # denominator is as steady as the numerator
    n_base = min(n_base, len(reqs))
    base_best = float("inf")
    for _ in range(base_repeats):
        t0 = time.perf_counter()
        for i in range(n_base):
            p = matcher.prepare(reqs[i]["trace"])
            valid = p.edge_ids != -1
            path, _ = viterbi_decode_numpy(p.dist_m, valid, p.route_m,
                                           p.gc_m, p.case, sigma, beta)
            match = assemble_segments(city, p, path)
            make_report(match, reqs[i], 15, {0, 1, 2}, {0, 1, 2})
        base_best = min(base_best, time.perf_counter() - t0)
    baseline_tps = n_base / base_best

    # -- batched leg: the production path end-to-end ----------------------
    matcher.match_many(reqs[:8])  # warmup: compile the bucket shapes
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        # opt-in device profile of one batched pass (TensorBoard/Perfetto
        # viewable via jax.profiler — utils/metrics.device_trace); a
        # profiler failure must not cost the artifact
        try:
            from reporter_tpu.utils.metrics import device_trace
            with device_trace(profile_dir):
                matcher.match_many(tb)
        except Exception as e:
            print(f"profile pass failed (continuing): {e}",
                  file=sys.stderr)
    best, stages = _time_batched_leg(matcher, tb, reqs, report_wire,
                                     repeats)
    batched_tps = n_traces / best

    # -- wire-backend split: native vs Python vs legacy dict --------------
    # one match pass, three serialisation legs over identical matches —
    # the tentpole's isolated win, committed next to the stage share
    report_writers = _time_report_writers(matcher.match_many(tb), reqs)

    # device-compute telemetry of the whole run (obs/profiler.py): a
    # steady-state bench should compile each decode shape exactly once
    # (in warmup) — recompiles here mean the timed legs paid XLA, and
    # padding_waste is the fixed-bucket overhead the artifact now
    # carries toward the variable-length bucketing work
    from reporter_tpu.obs import profiler
    prof = profiler.snapshot(n_events=0)
    compile_field = {
        "episodes": prof["compile_episodes"],
        "shapes": len(prof["shapes"]),
        "recompiles": sum(max(0, s["compiles"] - 1)
                          for s in prof["shapes"]),
        "compile_s": round(sum(s["compile_s"] for s in prof["shapes"]),
                           6),
        "padding_waste": prof["totals"]["padding_waste"],
    }

    # -- serving-tier batched-query pair (ISSUE 14) -----------------------
    # query_many(256) vs 256 singles over one synthetic store; parity
    # asserted inside the leg, ratio gated by perf_gate
    try:
        query_field = _query_leg()
    except Exception as e:  # record the failure, keep the artifact
        query_field = {"error": str(e)[:200]}

    # -- route-kernel triple (ISSUE 16) -----------------------------------
    # device relax vs host Dijkstra vs native memo on identical pairs;
    # parity asserted byte-identical inside the leg before any timing
    try:
        routes_field = _routes_leg(city, matcher, params, reqs)
    except Exception as e:  # record the failure, keep the artifact
        routes_field = {"error": str(e)[:200]}

    # -- optional second decode backend: the fused pallas kernel ----------
    # recorded in the same artifact so hardware claims in docstrings trace
    # to a committed number; default-on only where it runs compiled (tpu)
    pallas_field = None
    want_pallas = os.environ.get("BENCH_PALLAS",
                                 "1" if platform == "tpu" else "0")
    if want_pallas not in ("0", "off", "false"):
        saved = os.environ.get("REPORTER_TPU_DECODE")
        os.environ["REPORTER_TPU_DECODE"] = "pallas"
        try:
            matcher.match_many(reqs[:8])  # compile the pallas shapes
            p_best, p_stages = _time_batched_leg(
                matcher, tb, reqs, report_wire, max(2, repeats - 2))
            pallas_field = {"traces_per_sec": round(n_traces / p_best, 1),
                            "stages": p_stages}
        except Exception as e:  # record the failure, keep the artifact
            pallas_field = {"error": str(e)[:200]}
        finally:
            if saved is None:
                os.environ.pop("REPORTER_TPU_DECODE", None)
            else:
                os.environ["REPORTER_TPU_DECODE"] = saved

    print(json.dumps({
        "metric": f"synthetic-city traces/sec map-matched end-to-end "
                  f"(columnar prep+decode+assemble+report-serialise, "
                  f"T={T_bucket}, "
                  f"K={K}, platform={platform}, "
                  f"decode={decode_backend(T_bucket, K)}) "
                  f"batched match_many over a zero-dict TraceBatch vs "
                  f"single-process single-thread CPU numpy baseline "
                  f"(Meili-analog)",
        "value": round(batched_tps, 1),
        "unit": "traces/sec",
        "vs_baseline": round(batched_tps / baseline_tps, 2),
        "stages": stages,
        "report_writers": report_writers,
        "baseline": {"traces_per_sec": round(baseline_tps, 1),
                     "n_traces": n_base, "repeats": base_repeats},
        "compile": compile_field,
        "query": query_field,
        "routes": routes_field,
        "device": {"platform": platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "pallas": pallas_field,
    }))
    return 0


if __name__ == "__main__":
    if "--streaming" in sys.argv[1:]:
        # the incremental matcher's per-appended-point leg (ISSUE 19)
        # times growing windows, not bulk replays — its own module,
        # reachable as `python bench.py --streaming` for one-command
        # symmetry with the throughput legs
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools import stream_bench
        sys.exit(stream_bench.main(sys.argv[1:]))
    if "--feed-fanout" in sys.argv[1:]:
        # the freshness tier's fan-out leg (ISSUE 18) lives in its own
        # module — a serving bench like tools/prefork_bench.py, not a
        # matcher throughput leg — but rides bench.py's front door so
        # `python bench.py --feed-fanout 1000` is one command
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools import feed_fanout_bench
        sys.exit(feed_fanout_bench.main(sys.argv[1:]))
    sys.exit(main())
