#!/usr/bin/env python
"""Observability smoke: the obs layer proven over the real stack.

Four legs, each asserting a contract the README advertises:

  trace     a ``/report?trace=1`` request returns a Perfetto-loadable
            trace-event JSON whose stage spans cover >= 95% of the
            request's root span (no unexplained request time), naming
            every pipeline stage (dispatch -> matcher prep/decode/
            assemble -> serialisation)
  metrics   ``/metrics`` scrapes clean: every line parses as Prometheus
            exposition 0.0.4, histogram buckets are monotone and end at
            the +Inf == _count invariant; ``/stats`` reports
            p50/p95/p99 per stage timer
  slo       a breached ``REPORTER_TPU_SLO_MS`` budget flips /health 503
            with the breach named; clearing it restores 200
  profiler  a second same-shape request adds ZERO
            ``decode.compile.count`` (recompile-count stability);
            ``/profile`` scrapes clean and reports a padding-waste
            ratio in (0, 1) for a mixed-length batch; and
            ``REPORTER_TPU_SHADOW_SAMPLE=1.0`` over the synthetic city
            yields ``decode.shadow.mismatch == 0`` with a non-zero
            sample count
  perf_gate ``tools/perf_gate.py`` passes against a ledger freshly
            seeded from the checked-in bench artifacts, and a doctored
            candidate 20% below the ledger median fails it
  flightrec a worker SIGKILL'd by a deterministic crash failpoint
            (``worker.offer=crash``) leaves a flight-recorder
            postmortem naming the exact span in flight at death

Usage: REPORTER_TPU_PLATFORM=cpu python tools/obs_smoke.py
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("REPORTER_TPU_PLATFORM", "cpu")

FMT = r",sv,\|,0,1,2,3,4"  # uuid|lat|lon|time|accuracy

#: every stage the trace must make legible for a single /report request
REQUIRED_SPANS = ("service.request", "service.parse", "service.handle",
                  "dispatch.batch", "dispatch.match_many",
                  "matcher.chunk", "matcher.prep",
                  "matcher.decode_dispatch", "matcher.decode_wait",
                  "matcher.assemble", "report.serialise")

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+( Inf)?$')
_META_RE = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ")


def log(msg: str) -> None:
    print(f"obs_smoke: {msg}", flush=True)


def fail(msg: str) -> int:
    sys.stderr.write(f"obs_smoke: FAIL: {msg}\n")
    return 1


def _city():
    from reporter_tpu.synth import build_grid_city
    return build_grid_city(rows=10, cols=10, spacing_m=200.0, seed=5,
                           service_road_fraction=0.0,
                           internal_fraction=0.0)


def _request(city, uuid: str, seed: int) -> dict:
    import numpy as np

    from reporter_tpu.synth import generate_trace
    rng = np.random.default_rng(seed)
    tr = None
    while tr is None:
        tr = generate_trace(city, uuid, rng, noise_m=3.0,
                            min_route_edges=8)
    return {"uuid": tr.uuid, "trace": tr.points,
            "match_options": {"mode": "auto", "report_levels": [0, 1, 2],
                              "transition_levels": [0, 1, 2]}}


def _post(url: str, body: dict):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return resp.status, resp.read().decode()


def _coverage(events) -> float:
    """Fraction of the root span's wall covered by the union of every
    other span's interval — the "no unexplained request time" number."""
    root = [e for e in events if e["name"] == "service.request"]
    if not root:
        return 0.0
    r0 = root[0]["ts"]
    r1 = r0 + root[0]["dur"]
    ivals = sorted(
        (max(e["ts"], r0), min(e["ts"] + e["dur"], r1))
        for e in events
        if e is not root[0] and e.get("ph") == "X"
        and e["ts"] + e["dur"] > r0 and e["ts"] < r1)
    covered = 0.0
    cur0 = cur1 = None
    for a, b in ivals:
        if cur1 is None or a > cur1:
            if cur1 is not None:
                covered += cur1 - cur0
            cur0, cur1 = a, b
        else:
            cur1 = max(cur1, b)
    if cur1 is not None:
        covered += cur1 - cur0
    return covered / max(root[0]["dur"], 1e-9)


def check_exposition(text: str) -> str:
    """Parse a Prometheus text body; returns "" when clean, else the
    first problem. Validates line grammar, bucket monotonicity and the
    +Inf == _count histogram invariant."""
    buckets: dict = {}
    counts: dict = {}
    for i, line in enumerate(text.strip("\n").split("\n"), start=1):
        if _META_RE.match(line):
            continue
        if not _SAMPLE_RE.match(line):
            return f"line {i} is not exposition format: {line!r}"
        name = line.split("{")[0].split(" ")[0]
        value = float(line.rsplit(" ", 1)[1])
        if name.endswith("_bucket"):
            fam = buckets.setdefault(name, [])
            if fam and value < fam[-1]:
                return f"bucket counts not monotone at line {i}: {line!r}"
            fam.append(value)
        elif name.endswith("_count"):
            counts[name[:-len("_count")]] = value
        if value < 0:
            return f"negative sample at line {i}: {line!r}"
    for fam, vals in buckets.items():
        base = fam[:-len("_bucket")]
        if base in counts and vals[-1] != counts[base]:
            return (f"{fam} +Inf bucket {vals[-1]} != "
                    f"{base}_count {counts[base]}")
    return ""


# ---------------------------------------------------------------------------
def leg_service() -> int:
    """trace + metrics + slo legs over one in-process HTTP service."""
    from reporter_tpu.matcher import SegmentMatcher
    from reporter_tpu.service.server import ReporterService, serve

    city = _city()
    service = ReporterService(SegmentMatcher(net=city), threshold_sec=15,
                              max_batch=64, max_wait_ms=5.0)
    httpd = serve(service, "127.0.0.1", 0)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        req = _request(city, "obs-0", seed=3)

        # -- trace leg ------------------------------------------------------
        code, _ = _post(f"{base}/report", req)  # warm the jit caches
        if code != 200:
            return fail(f"warmup request failed ({code})")
        t0 = time.perf_counter()
        code, text = _post(f"{base}/report?trace=1", req)
        wall_s = time.perf_counter() - t0
        if code != 200:
            return fail(f"traced request failed ({code})")
        body = json.loads(text)
        if "report" not in body or "trace" not in body:
            return fail(f"?trace=1 response missing report/trace keys: "
                        f"{sorted(body)}")
        if "datastore" not in body["report"]:
            return fail("?trace=1 report payload lost the report schema")
        events = body["trace"].get("traceEvents")
        if not events:
            return fail("empty traceEvents")
        for ev in events:  # Perfetto-loadable: the fields it requires
            if not (ev.get("name") and ev.get("ph") in ("X", "B")
                    and isinstance(ev.get("ts"), (int, float))
                    and isinstance(ev.get("pid"), int)):
                return fail(f"malformed trace event: {ev}")
            if ev["ph"] == "X" and not isinstance(ev.get("dur"),
                                                  (int, float)):
                return fail(f"X event without dur: {ev}")
        names = {e["name"] for e in events}
        missing = [n for n in REQUIRED_SPANS if n not in names]
        if missing:
            return fail(f"trace is missing stage spans {missing}; "
                        f"got {sorted(names)}")
        root_s = next(e["dur"] for e in events
                      if e["name"] == "service.request") / 1e6
        cov = _coverage(events)
        if cov < 0.95:
            return fail(f"stage spans cover only {cov:.1%} of the "
                        f"request root span (want >= 95%)")
        log(f"trace: {len(events)} events, stage coverage {cov:.1%} of "
            f"root ({root_s * 1e3:.1f} ms of {wall_s * 1e3:.1f} ms wall)")

        # -- metrics leg ----------------------------------------------------
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            ctype = resp.headers["Content-type"]
            mtext = resp.read().decode()
        if not ctype.startswith("text/plain"):
            return fail(f"/metrics content type {ctype!r}")
        problem = check_exposition(mtext)
        if problem:
            return fail(f"/metrics not scrape-clean: {problem}")
        for needle in ("reporter_tpu_service_requests_total",
                       "reporter_tpu_service_handle_seconds_bucket",
                       "reporter_tpu_service_handle_seconds_sum",
                       "reporter_tpu_service_handle_seconds_count"):
            if needle not in mtext:
                return fail(f"/metrics missing {needle}")
        with urllib.request.urlopen(f"{base}/stats") as resp:
            stats = json.loads(resp.read().decode())
        handle = stats["timers"].get("service.handle")
        if not handle:
            return fail("no service.handle timer in /stats")
        for key in ("p50_s", "p95_s", "p99_s"):
            if key not in handle:
                return fail(f"/stats timer missing {key}: {handle}")
        if not (handle["p50_s"] <= handle["p95_s"] <= handle["p99_s"]
                <= handle["max_s"]):
            return fail(f"percentiles not ordered: {handle}")
        log(f"metrics: scrape-clean exposition "
            f"({len(mtext.splitlines())} lines), /stats p99 "
            f"{handle['p99_s'] * 1e3:.1f} ms over {handle['count']} "
            "requests")

        # -- slo leg --------------------------------------------------------
        os.environ["REPORTER_TPU_SLO_MS"] = "service.handle=0.000001"
        try:
            try:
                urllib.request.urlopen(f"{base}/health")
                return fail("breached SLO did not flip /health 503")
            except urllib.error.HTTPError as e:
                if e.code != 503:
                    return fail(f"/health {e.code} on SLO breach")
                hbody = json.loads(e.read().decode())
                breaches = hbody.get("slo", {}).get("breaches")
                if not breaches or \
                        breaches[0]["stage"] != "service.handle":
                    return fail(f"breach not named on /health: {hbody}")
        finally:
            os.environ.pop("REPORTER_TPU_SLO_MS", None)
        with urllib.request.urlopen(f"{base}/health") as resp:
            if resp.status != 200:
                return fail("/health did not recover after SLO cleared")
        log("slo: breach flipped /health 503 and named the stage; "
            "clearing the spec restored 200")

        # -- profiler leg ---------------------------------------------------
        from reporter_tpu.obs import profiler

        def counters():
            with urllib.request.urlopen(f"{base}/stats") as resp:
                return json.loads(resp.read().decode())["counters"]

        c0 = counters().get("decode.compile.count", 0)
        if c0 < 1:
            return fail("no compile episode recorded for the first "
                        "requests (compile telemetry dead?)")
        code, _ = _post(f"{base}/report", req)  # SAME shape again
        if code != 200:
            return fail(f"repeat request failed ({code})")
        c1 = counters().get("decode.compile.count", 0)
        if c1 != c0:
            return fail(f"second same-shape request recompiled: "
                        f"decode.compile.count {c0} -> {c1}")

        # mixed-length batch through one dispatcher round trip. The
        # chunk plan decodes the three as ONE chunk (a split would save
        # far fewer padded cells than a chunk costs), so the wide-event
        # assertions below see a >=2-trace chunk
        mixed = []
        for i, n_pts in enumerate((12, 40, 45)):
            r = _request(city, f"mix-{i}", seed=20 + i)
            r["trace"] = r["trace"][:n_pts]
            mixed.append(r)
        os.environ["REPORTER_TPU_SHADOW_SAMPLE"] = "1.0"
        try:
            reports = service.report_many(mixed)
            if not all(r is not None for r in reports):
                return fail("mixed-length batch had failed reports")
            if not profiler.drain_shadow(60.0):
                return fail("shadow decode did not drain")
        finally:
            os.environ.pop("REPORTER_TPU_SHADOW_SAMPLE", None)

        with urllib.request.urlopen(f"{base}/profile") as resp:
            prof = json.loads(resp.read().decode())
        for key in ("shapes", "events", "totals", "shadow",
                    "compile_episodes"):
            if key not in prof:
                return fail(f"/profile missing {key}: {sorted(prof)}")
        if not prof["shapes"] or not prof["events"]:
            return fail("/profile has no shapes/events after requests")
        mixed_evs = [e for e in prof["events"] if e["traces"] >= 2]
        if not mixed_evs:
            return fail("no multi-trace wide event for the mixed batch")
        waste = mixed_evs[-1]["padding_waste"]
        if not (0.0 < waste < 1.0):
            return fail(f"mixed-batch padding waste {waste} not in "
                        "(0, 1)")
        cnt = counters()
        sampled = cnt.get("decode.shadow.sampled", 0)
        mismatch = cnt.get("decode.shadow.mismatch", 0)
        if sampled < len(mixed):
            return fail(f"shadow sampled only {sampled} traces")
        if mismatch != 0:
            return fail(f"shadow oracle disagreed on {mismatch} of "
                        f"{sampled} traces (accuracy drift!)")
        storms = sum(max(0, s["compiles"] - 1) for s in prof["shapes"])
        if storms:
            return fail(f"recompile storm: {storms} same-shape "
                        f"recompiles in {prof['shapes']}")
        log(f"profiler: compile stable at {c1} episode(s) across "
            f"repeat requests, mixed-batch padding waste {waste:.3f}, "
            f"shadow {sampled} sampled / 0 mismatches")
        return 0
    finally:
        httpd.shutdown()


# ---------------------------------------------------------------------------
def leg_perf_gate() -> int:
    """The perf ledger/gate contract: a seeded ledger passes the
    self-check; a candidate 20% below the ledger median fails."""
    with tempfile.TemporaryDirectory() as tmp:
        ledger = os.path.join(tmp, "LEDGER.jsonl")
        seed = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "perf_ledger.py"),
             "seed", "--out", ledger, "--repo", REPO],
            capture_output=True, text=True, timeout=60)
        if seed.returncode != 0:
            return fail(f"perf_ledger seed rc={seed.returncode}: "
                        f"{seed.stderr[-500:]}")
        ok = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
             "--ledger", ledger, "--self-check"],
            capture_output=True, text=True, timeout=60)
        if ok.returncode != 0:
            return fail(f"perf_gate self-check failed on a clean "
                        f"ledger: {ok.stdout[-500:]}{ok.stderr[-500:]}")
        # doctor a candidate 20% below the cpu full-run median
        import statistics
        with open(ledger, encoding="utf-8") as f:
            entries = [json.loads(line) for line in f if line.strip()]
        pool = [e["vs_baseline"] for e in entries
                if e.get("vs_baseline") and e.get("platform") == "cpu"
                and e.get("scope", "full") == "full"]
        median = statistics.median(pool)
        doctored = os.path.join(tmp, "doctored.json")
        with open(doctored, "w", encoding="utf-8") as f:
            json.dump({"source": "doctored", "platform": "cpu",
                       "scope": "full", "pipelined": False,
                       "vs_baseline": round(median * 0.8, 2),
                       "stage_shares": None, "kind": "bench"}, f)
        bad = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
             "--ledger", ledger, "--candidate", doctored],
            capture_output=True, text=True, timeout=60)
        if bad.returncode == 0:
            return fail(f"perf_gate PASSED a 20%-regressed candidate "
                        f"(median {median}): {bad.stdout[-500:]}")
        log(f"perf_gate: clean self-check passed; 20%-regressed "
            f"candidate ({median:.2f} -> {median * 0.8:.2f}) failed "
            "as it must")
        return 0


# ---------------------------------------------------------------------------
def leg_flightrec() -> int:
    """A crash failpoint mid-stream leaves a postmortem naming the span
    in flight at SIGKILL."""
    import numpy as np

    from reporter_tpu.synth import generate_trace
    from reporter_tpu.utils import faults as faults_mod

    with tempfile.TemporaryDirectory() as tmp:
        city = _city()
        graph = os.path.join(tmp, "city.npz")
        city.save(graph)
        rng = np.random.default_rng(9)
        lines = []
        for i in range(4):
            tr = None
            while tr is None:
                tr = generate_trace(city, f"veh-{i}", rng, noise_m=3.0,
                                    min_route_edges=8)
            for p in tr.points:
                lines.append("|".join(
                    [tr.uuid, str(p["lat"]), str(p["lon"]),
                     str(p["time"]), str(p["accuracy"])]))
        inp = os.path.join(tmp, "input.txt")
        with open(inp, "w") as f:
            f.write("\n".join(lines) + "\n")
        out = os.path.join(tmp, "out")
        k = len(lines) // 2
        env = dict(os.environ,
                   REPORTER_TPU_PLATFORM="cpu",
                   REPORTER_TPU_TRACE="1",
                   REPORTER_TPU_FAULTS=f"worker.offer=crash+{k}#1")
        cmd = [sys.executable, "-m", "reporter_tpu", "stream",
               "-f", FMT, "--graph", graph, "-p", "1", "-q", "3600",
               "-i", "1000000000", "-s", "obs", "-o", out,
               "--input", inp, "--uuid-filter", "off",
               "-r", "0,1,2", "-x", "0,1,2"]
        p = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=600)
        if p.returncode != faults_mod.CRASH_EXIT_CODE:
            return fail(f"crash run rc={p.returncode} "
                        f"(want {faults_mod.CRASH_EXIT_CODE}): "
                        f"{p.stderr[-2000:]}")
        rec_dir = os.path.join(out, ".deadletter", ".flightrec")
        dumps = sorted(os.listdir(rec_dir)) if os.path.isdir(rec_dir) \
            else []
        if not dumps:
            return fail(f"no flight-recorder dump under {rec_dir}")
        with open(os.path.join(rec_dir, dumps[-1]),
                  encoding="utf-8") as f:
            post = json.load(f)
        if not post["reason"].startswith("crash.worker.offer"):
            return fail(f"postmortem reason {post['reason']!r}")
        inflight = [s["name"] for s in post.get("in_flight", [])]
        if "worker.offer" not in inflight:
            return fail(f"postmortem does not name the span in flight "
                        f"at SIGKILL: {inflight}")
        if len(post.get("spans", [])) == 0:
            return fail("postmortem ring is empty (tracing was armed)")
        log(f"flightrec: postmortem {dumps[-1]} names in-flight span "
            f"worker.offer with {len(post['spans'])} ring events")
        return 0


def main(argv=None) -> int:
    rc = leg_service()
    if rc:
        return rc
    rc = leg_perf_gate()
    if rc:
        return rc
    rc = leg_flightrec()
    if rc:
        return rc
    log("all legs passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
