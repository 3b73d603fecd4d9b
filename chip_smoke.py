#!/usr/bin/env python
"""Chip smoke: the main path end to end on one TPU chip at metro scale.

Drives the system through the entry points a user calls, on a 150 x 150
grid city (22,500 nodes, 89,400 directed edges, ~30 x 30 km around the
synth default, Manila) with 2,048 synthetic vehicles at 1 Hz, 4 m noise,
64-256 points each, and the matcher's default ``MatchParams`` (K=8,
50 m search radius):

- **serve**: the service ``python -m reporter_tpu serve`` builds
  (``service.server.make_service`` + ``make_server``) on a local port;
  one warm pass, then every trace POSTed to ``/report`` from 32 client
  threads, with every decoded chunk shadow-re-scored against the numpy
  oracle (``REPORTER_TPU_SHADOW_SAMPLE=1.0``).
- **route**: the same on a second service with the device route kernel
  (``REPORTER_TPU_ROUTE_DEVICE=1``); its segment ids must equal serve's.
- **stream**: ``streaming.worker.main`` over a file of 512 vehicles'
  probes, in-process matching (incremental advance kernel), tiles to
  disk over three or more flush intervals.

Any fallback fails the smoke: a platform other than ``tpu``, a native
runtime that did not load, a non-200 response, a non-zero breaker /
fallback / recompile counter (:data:`MUST_BE_ZERO`), a shadow mismatch,
segment agreement with the ground truth below 0.99. The last line of
stdout is ``{"ok": true, "device": {...}}`` only when everything held.

``--chips 4`` runs only the sharded decode: the same 2,048 traces
through ``match_many`` on the 4-chip data mesh, then on one chip
(``REPORTER_TPU_DEVICE_SLICE=0:1``), report bodies compared byte for
byte. ``--rehearse`` runs on whatever JAX finds (the CPU here, with
``--rows``/``--traces`` to shrink it) and never prints the ok line.
Counters are checked over the warm pass too; the measured pass of each
serving leg must compile nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
FMT = ",sv,\\|,0,1,2,3,4"
MIN_AGREEMENT = 0.99  # the accuracy gate (tools/accuracy_cli.py, ci.yml)
CLIENTS = 32           # /report client threads: the dispatcher micro-batches
STREAM_VEHICLES = 512  # vehicles the streaming leg replays

#: counters that name a fallback, a breaker failure, a quarantine or a
#: recompile: any non-zero value fails the smoke
MUST_BE_ZERO = (
    "matcher.circuit.failures",
    "matcher.circuit.fallback_chunks",
    "matcher.circuit.native_errors",
    "matcher.circuit.decode.failures",
    "matcher.circuit.decode.errors",
    "matcher.circuit.decode.fallback_chunks",
    "matcher.circuit.assemble.failures",
    "matcher.circuit.assemble.native_errors",
    "matcher.circuit.assemble.fallback_chunks",
    "matcher.assemble.quarantined",
    "matcher.circuit.route.failures",
    "route.device.errors",
    "route.device.fallback_chunks",
    "route.device.build_errors",
    "route.device.finalize_errors",
    "route.device.circuit_skipped_chunks",
    "pressure.oracle_chunks",
    "wire.circuit.failures",
    "wire.errors",
    "wire.fallback",
    "matcher.circuit.incremental.failures",
    "match.incremental.errors",
    "match.incremental.circuit_skips",
    "match.incremental.shadow_mismatches",
    "decode.compile.recompiles",
    "decode.shadow.errors",
    "decode.shadow.mismatch",
)


class SmokeFailure(Exception):
    pass


def say(leg: str, **fields) -> None:
    print(f"smoke {leg}: " + json.dumps(fields), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Every backend compile in the process (decode, route kernel,
    incremental advance), from ``jax.monitoring``: the event fires for
    a persistent-cache hit too, so a new executable counts either way."""

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.names = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, dur, fun_name="?", **_kw):
        if name.endswith("backend_compile_duration"):
            with self._lock:
                self.names.append(fun_name)
                self.seconds += dur

    def read(self):
        with self._lock:
            return len(self.names), self.seconds

    def since(self, n: int) -> dict:
        """Compiled function name -> count, for compiles after the n-th."""
        with self._lock:
            names = self.names[n:]
        return {f: names.count(f) for f in sorted(set(names))}


def counters() -> dict:
    from reporter_tpu.utils import metrics
    return metrics.default.snapshot()["counters"]


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def check_zero(leg: str, d: dict) -> None:
    bad = {k: d[k] for k in MUST_BE_ZERO if d.get(k)}
    check(not bad, f"{leg}: fallback counters non-zero: {bad}")


def build_traffic(net, rows: int, n: int, seed: int) -> list:
    """``n`` vehicles between nearby intersections (4-20 blocks apart,
    so each route search stays local on the metro graph), kept when the
    trace has 64-256 points."""
    import numpy as np

    from reporter_tpu.synth import generate_trace
    rng = np.random.default_rng(seed)
    traces = []
    while len(traces) < n:
        r, c = (int(x) for x in rng.integers(0, (rows, rows)))
        d = int(rng.integers(4, 21))
        dr = int(rng.integers(-d, d + 1))
        dc = (d - abs(dr)) * int(rng.choice((-1, 1)))
        if not (0 <= r + dr < rows and 0 <= c + dc < rows):
            continue
        tr = generate_trace(net, f"veh-{len(traces)}", rng, noise_m=4.0,
                            min_route_edges=1, max_route_edges=10 ** 6,
                            endpoints=(r * rows + c,
                                       (r + dr) * rows + c + dc))
        if tr is not None and 64 <= len(tr.points) <= 256:
            traces.append(tr)
    return traces


def post_all(port: int, bodies: list, clients: int) -> list:
    """POST every body to /report from ``clients`` threads; returns
    (status, response bytes) per body, status -1 on a transport error."""
    out = [None] * len(bodies)

    def client(ci: int) -> None:
        for i in range(ci, len(bodies), clients):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/report", data=bodies[i],
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=600) as r:
                    out[i] = (r.status, r.read())
            except urllib.error.HTTPError as e:
                out[i] = (e.code, e.read())
            except Exception as e:
                out[i] = (-1, repr(e).encode())

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def shape_pools(traces: list) -> dict:
    """Trace indices by the decode bucket each can land in: its ladder
    bucket, or the power-of-two sub-bucket the matcher splits a
    wasteful group into (``SegmentMatcher._split_bucket``)."""
    from reporter_tpu.matcher.batchpad import bucket_ladder
    ladder = bucket_ladder()[0]
    pools = {}
    for i, tr in enumerate(traces):
        n = len(tr.points)
        top = next((b for b in ladder if b >= n), ladder[-1])
        T = min(max(1 << (n - 1).bit_length(), ladder[0]), top)
        pools.setdefault(T, []).append(i)
    return pools


def warm_shapes(service, reqs: list, pools: dict) -> int:
    """Every (rows, T) shape the measured pass can meet: per bucket, one
    ``report_many`` batch (the service's in-process entry point, one
    dispatcher batch) of each power-of-two size up to the dispatcher's
    cap. A batch of up to 128 traces may be merged into one chunk at
    its longest trace's bucket, whatever the size of that bucket's pool,
    so every bucket is warmed at every size. The ladder is pinned to
    the one bucket with splitting off meanwhile, so a batch decodes at
    that bucket whatever its traces (a longer trace is cut to it).
    Returns the batches sent."""
    from reporter_tpu.matcher.batchpad import ENV_BUCKETS
    sent = 0
    ladder = os.environ.get(ENV_BUCKETS)
    try:
        for T in sorted(pools):
            os.environ[ENV_BUCKETS] = f"{T}@off"
            r = 1
            while r <= min(service.dispatcher.max_batch, len(reqs)):
                got = service.report_many(reqs[:r])
                check(all(g is not None for g in got),
                      f"warm batch of {r}: {sum(g is None for g in got)} "
                      "reports failed")
                sent += 1
                r *= 2
    finally:
        if ladder is None:
            os.environ.pop(ENV_BUCKETS)
        else:
            os.environ[ENV_BUCKETS] = ladder
    return sent


def serve_leg(leg: str, conf: dict, bodies: list, reqs: list, pools: dict,
              clock: CompileClock) -> list:
    """One service instance: warm pass (every batch shape, then every
    body over HTTP), then the measured pass with every decoded chunk
    shadow-re-scored. The fallback counters cover both passes; the
    measured pass must compile nothing. Returns the parsed bodies."""
    from reporter_tpu.obs import profiler
    from reporter_tpu.service.server import make_server, make_service

    service = make_service(conf)
    httpd = make_server(service, "127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    max_pending = profiler._SHADOW_MAX_PENDING
    try:
        start = counters()
        c0, s0 = clock.read()
        t0 = time.perf_counter()
        warm_batches = warm_shapes(service, reqs, pools)
        warm = post_all(port, bodies, CLIENTS)
        warm_s = time.perf_counter() - t0
        bad = [r for r in warm if r[0] != 200]
        check(not bad, f"{leg} warm pass: {len(bad)} non-200 responses "
                       f"(first: {bad[:1]})")
        check_zero(f"{leg} warm pass", delta(start, counters()))
        c1, s1 = clock.read()

        os.environ[profiler.ENV_SHADOW] = "1.0"
        # every chunk re-scored: queue the oracle's backlog instead of
        # shedding it (its one thread gets little of the GIL while 32
        # clients and the server run in this process); it catches up in
        # drain_shadow, timed apart from serving
        profiler._SHADOW_MAX_PENDING = len(bodies)
        before = counters()
        t0 = time.perf_counter()
        got = post_all(port, bodies, CLIENTS)
        wall = time.perf_counter() - t0
        t1 = time.perf_counter()
        drained = profiler.drain_shadow(timeout_s=600.0)
        drain_s = time.perf_counter() - t1
        c2, s2 = clock.read()
        after = counters()
    finally:
        os.environ.pop(profiler.ENV_SHADOW, None)
        profiler._SHADOW_MAX_PENDING = max_pending
        httpd.shutdown()
        httpd.server_close()
        service.dispatcher.close()

    d = delta(before, after)
    non200 = [r for r in got if r[0] != 200]
    rescored = d.get("decode.shadow.chunks", 0)
    dropped = d.get("decode.shadow.dropped", 0)
    steady_compiles = clock.since(c1)
    say(leg, traces=len(bodies), clients=CLIENTS,
        warm_batches=warm_batches, warm_s=warm_s, warm_compiles=c1 - c0,
        warm_compile_s=s1 - s0,
        wall_s=wall, traces_per_s=len(bodies) / wall,
        shadow_drain_s=drain_s,
        compiles=c2 - c1, compile_s=s2 - s1,
        steady_compiled=steady_compiles,
        decode_compile_episodes=d.get("decode.compile.count", 0),
        decode_chunks=d.get("profile.chunks", 0),
        shadow_rescored_chunks=rescored, shadow_dropped_chunks=dropped,
        shadow_traces=d.get("decode.shadow.sampled", 0),
        route_device_chunks=d.get("route.device.chunks", 0),
        route_budget_exceeded=d.get("route.device.budget_exceeded", 0),
        route_relax_blocks=d.get("route.device.relax_blocks", 0),
        non200=len(non200))
    check(not non200, f"{leg}: {len(non200)} non-200 responses (first: "
                      f"{non200[:1]})")
    check_zero(leg, delta(start, after))
    check(c2 == c1, f"{leg}: {c2 - c1} compiles in the steady pass over "
                    f"the warm pass's traffic: {steady_compiles}")
    check(drained, f"{leg}: shadow oracle did not drain")
    check(rescored > 0 and rescored >= dropped,
          f"{leg}: shadow re-scored {rescored} chunks, dropped {dropped} "
          "(need at least half re-scored)")
    return [json.loads(body) for _, body in got]


def stream_leg(graph: str, traces: list, tmp: str) -> dict:
    """streaming.worker.main over a time-ordered probe file."""
    from reporter_tpu.streaming import worker

    rows = sorted((p["time"], tr.uuid, p) for tr in traces
                  for p in tr.points)
    src = os.path.join(tmp, "probes.psv")
    with open(src, "w") as f:
        for _t, uuid, p in rows:
            f.write(f"{uuid}|{p['lat']}|{p['lon']}|{p['time']}|"
                    f"{p['accuracy']}\n")
    out = os.path.join(tmp, "tiles")
    flush_s = 1
    before = counters()
    t0 = time.perf_counter()
    rc = worker.main(["-f", FMT, "--graph", graph, "-p", "1",
                      "-q", "3600", "-i", str(flush_s), "-s", "smoke",
                      "-o", out, "--input", src, "-r", "0,1,2",
                      "-x", "0,1,2", "--report-flush-interval", "0.25"])
    wall = time.perf_counter() - t0
    d = delta(before, counters())
    tiles = [os.path.join(r, f) for r, _d, fs in os.walk(out) for f in fs
             if ".deadletter" not in r]
    epochs = {m.group(1) for t in tiles
              for m in [re.search(r"\.e(\d{8})$", t)] if m}
    say("stream", vehicles=len(traces), messages=len(rows), rc=rc,
        wall_s=wall, flush_interval_s=flush_s,
        incremental_steps=d.get("match.incremental.steps", 0),
        incremental_matches=d.get("match.incremental.matches", 0),
        incremental_fallbacks=d.get("match.incremental.fallbacks", 0),
        incremental_commits=d.get("match.incremental.commits", 0),
        decode_chunks=d.get("profile.chunks", 0),
        tiles_written=len(tiles), flush_epochs_with_tiles=len(epochs))
    check(rc == 0, f"stream: worker exited {rc}")
    check_zero("stream", d)
    check(d.get("match.incremental.steps", 0) > 0,
          "stream: no incremental advance ran")
    check(len(epochs) >= 3, f"stream: tiles from {len(epochs)} flush "
                            "intervals, need 3 or more")
    return d


def segment_ids(body: dict) -> list:
    return [s.get("segment_id") for s in body["segment_matcher"]["segments"]]


def one_chip(net, graph: str, traces: list, clock, tmp: str) -> None:
    from reporter_tpu.matcher import Configure
    from reporter_tpu.tools.accuracy_cli import score_matches

    conf = {"graph": graph}
    Configure(conf)
    reqs = [tr.request_json() for tr in traces]
    bodies = [json.dumps(r).encode() for r in reqs]
    pools = shape_pools(traces)
    say("buckets", **{str(T): len(idx) for T, idx in sorted(pools.items())})

    host = serve_leg("serve", conf, bodies, reqs, pools, clock)
    acc = score_matches(net, [b["segment_matcher"] for b in host], traces)
    say("accuracy", **acc)
    check(acc["agreement"] >= MIN_AGREEMENT,
          f"segment agreement {acc['agreement']} < {MIN_AGREEMENT}")

    os.environ["REPORTER_TPU_ROUTE_DEVICE"] = "1"
    try:
        dev = serve_leg("route", conf, bodies, reqs, pools, clock)
    finally:
        os.environ.pop("REPORTER_TPU_ROUTE_DEVICE")
    differ = sum(segment_ids(a) != segment_ids(b)
                 for a, b in zip(host, dev))
    say("route-parity", traces=len(dev), segment_ids_differ=differ)
    check(differ == 0, f"route kernel: {differ} traces' segment ids "
                       "differ from host routes")
    check(counters().get("route.device.chunks", 0) > 0,
          "route: the device route kernel served no chunk")

    stream_leg(graph, traces[:STREAM_VEHICLES], tmp)

    from reporter_tpu.obs import profiler
    check(profiler.drain_shadow(timeout_s=600.0), "shadow did not drain")
    check(profiler.shadow_mismatches() == 0,
          f"{profiler.shadow_mismatches()} shadow mismatches")


def _decode_on(devslice: str, *args):
    """ops.decode_batch on the mesh a device slice names ("" = all)."""
    from reporter_tpu import ops
    os.environ["REPORTER_TPU_DEVICE_SLICE"] = devslice
    ops.reset_sharded_cache()
    return ops.decode_batch(*args)


def count_beyond_tolerance(matcher, reqs: list) -> int:
    """Decode-level re-score of traces whose bodies differ: the 4-chip
    and the one-chip path scored in f64 by the shadow sampler's scorer;
    returns how many differ by more than its tolerance."""
    import numpy as np

    from reporter_tpu.matcher.batchpad import pack_batches
    from reporter_tpu.matcher.hmm import NORMAL, SKIP, UNREACHABLE_THRESHOLD
    from reporter_tpu.obs import profiler

    sigma = np.float32(matcher.params.effective_sigma)
    beta = np.float32(matcher.params.beta)
    prepped = [matcher.prepare(r["trace"]) for r in reqs]
    worse = 0
    for batch in pack_batches(prepped, pad_batch_to=4, pad_pow2=True):
        args = (batch.dist_m, batch.valid, batch.route_m, batch.gc_m,
                batch.case, sigma, beta)
        paths = [np.asarray(_decode_on(sl, *args)[0]) for sl in ("", "0:1")]
        dist, route = np.asarray(batch.dist_m), np.asarray(batch.route_m)
        gc, case = np.asarray(batch.gc_m), np.asarray(batch.case)
        for b in range(len(batch.traces)):
            n = int(np.count_nonzero(case[b] != SKIP))
            s4, s1 = (profiler._path_score_f64(
                dist[b], route[b], gc[b], case[b], p[b], float(sigma),
                float(beta), n, NORMAL, UNREACHABLE_THRESHOLD)
                for p in paths)
            worse += not abs(s4 - s1) <= profiler.SHADOW_SCORE_TOL
    return worse


def four_chips(net, traces: list, clock) -> None:
    """The sharded decode on the 4-chip data mesh vs one chip."""
    from unittest import mock

    import jax

    from reporter_tpu import ops
    from reporter_tpu.matcher import SegmentMatcher
    from reporter_tpu.service.report import report_wire

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, JAX has "
                                   f"{len(jax.devices())}")
    reqs = [tr.request_json() for tr in traces]

    def run(label: str):
        ops.reset_sharded_cache()
        m = SegmentMatcher(net=net)
        spans = []
        real = ops.decode_batch

        def spy(*a, **kw):
            out = real(*a, **kw)
            spans.append(len(out[0].sharding.device_set))
            return out

        before = counters()
        c0, s0 = clock.read()
        with mock.patch.object(ops, "decode_batch", side_effect=spy):
            m.match_many(reqs)  # warm: compiles every shape
            cw, _sw = clock.read()
            t0 = time.perf_counter()
            matches = m.match_many(reqs)
            wall = time.perf_counter() - t0
        c1, s1 = clock.read()
        d = delta(before, counters())
        bodies = [bytes(report_wire(mt, rq, 15,
                                    set(rq["match_options"]["report_levels"]),
                                    set(rq["match_options"]
                                        ["transition_levels"])))
                  for mt, rq in zip(matches, reqs)]
        say(label, traces=len(reqs), wall_s=wall,
            traces_per_s=len(reqs) / wall, compiles=c1 - c0,
            compile_s=s1 - s0, decode_calls=len(spans),
            devices_spanned=sorted(set(spans)),
            shard_chunks=d.get("decode.shard.chunks", 0))
        check_zero(label, d)
        check(c1 == cw, f"{label}: {c1 - cw} compiles in the steady pass: "
                        f"{clock.since(cw)}")
        return m, bodies, spans, d

    _m4, b4, spans4, d4 = run("sharded-4")
    check(d4.get("decode.shard.chunks", 0) > 0, "no sharded decode chunk")
    check(spans4 and all(n == 4 for n in spans4),
          f"decode output spans {sorted(set(spans4))} devices, want 4")

    os.environ["REPORTER_TPU_DEVICE_SLICE"] = "0:1"
    try:
        m1, b1, spans1, _d1 = run("one-chip")
        check(spans1 and all(n == 1 for n in spans1),
              f"one-chip decode spans {sorted(set(spans1))} devices")
        differ = [i for i, (a, b) in enumerate(zip(b4, b1)) if a != b]
        worse = count_beyond_tolerance(m1, [reqs[i] for i in differ]) \
            if differ else 0
    finally:
        os.environ.pop("REPORTER_TPU_DEVICE_SLICE", None)
        ops.reset_sharded_cache()
    say("sharded-vs-one-chip", traces=len(reqs),
        bodies_identical=len(reqs) - len(differ),
        bodies_differ=len(differ), beyond_shadow_tolerance=worse)
    check(worse == 0, f"{worse} sharded paths score differently from "
                      "one chip beyond the shadow tolerance")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the platform JAX finds (CPU rehearsal); "
                   "never prints the ok line, exits 3 on success")
    p.add_argument("--rows", type=int, default=150,
                   help="grid city rows = columns")
    p.add_argument("--traces", type=int, default=2048)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, REPO)

    from reporter_tpu.utils import runtime
    if args.rehearse:
        if args.chips == 4:
            runtime.force_virtual_cpu(4)
        runtime.ensure_backend()
    else:
        runtime.ensure_backend("tpu")  # raises without a chip
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("device", **device)
    if not args.rehearse:
        check(dev.platform == "tpu", f"platform {dev.platform}, not tpu")
    clock = CompileClock()

    # the native host runtime, rebuilt from the committed source on THIS
    # machine: a library copied from elsewhere is never loaded by mtime,
    # and the Makefile picks -mf16c from this host's CPU flags
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-C",
                    os.path.join(REPO, "reporter_tpu", "native")],
                   check=True, stdout=subprocess.DEVNULL)
    native_s = time.perf_counter() - t0
    from reporter_tpu import native
    check(native.available(), "native host runtime did not load")

    from reporter_tpu.synth import build_grid_city
    t0 = time.perf_counter()
    net = build_grid_city(rows=args.rows, cols=args.rows, spacing_m=200.0,
                          seed=args.seed)
    traces = build_traffic(net, args.rows, args.traces, args.seed + 1)
    lengths = sorted(len(tr.points) for tr in traces)
    say("setup", native_build_s=native_s, nodes=int(net.num_nodes),
        edges=int(len(net.edge_start)), traces=len(traces),
        points_min=lengths[0], points_median=lengths[len(lengths) // 2],
        points_max=lengths[-1], setup_s=time.perf_counter() - t0,
        compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or runtime.DEFAULT_COMPILE_CACHE)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            four_chips(net, traces, clock)
        else:
            graph = os.path.join(tmp, "city.npz")
            net.save(graph)
            one_chip(net, graph, traces, clock, tmp)

    n, secs = clock.read()
    say("compile", total_compiles=n, total_compile_s=secs)
    if args.rehearse:
        print(f"smoke: rehearsal passed on {device['platform']}; no ok "
              "line off the chip", flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
