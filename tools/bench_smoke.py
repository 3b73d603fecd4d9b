#!/usr/bin/env python
"""CI smoke gate for the bench artifact's stage accounting.

Runs a deliberately tiny CPU-pinned bench (seconds, not minutes, one
repeat) and asserts the JSON contract future
tooling depends on: the artifact parses, carries the ``stages``
breakdown with the ``prep`` stage and its ``prep_share`` of batch wall
time, and records whether the chunked overlap path ran (``pipelined``).
A regression in stage accounting — a renamed timer, a dropped share
field, an artifact that stops being one JSON line — fails CI here
instead of silently degrading the committed BENCH artifacts.

``--out PATH`` additionally writes the artifact JSON to a file, which
is what the ``perf_gate`` CI stage consumes (tools/perf_gate.py gates
its vs_baseline ratio and stage shares against the LEDGER.jsonl
medians — ratios, never absolutes, so box drift can't flap it).
"""
import argparse
import json
import os
import subprocess
import sys

REQUIRED_TOP = ("metric", "value", "unit", "vs_baseline", "stages",
                "report_writers", "baseline", "device", "query", "routes")
REQUIRED_STAGES = ("prep", "decode_dispatch", "decode_wait", "assemble",
                   "report", "total", "prep_share", "report_share",
                   "pipelined")
# native prep phase split (candidates / select / routes) — present
# whenever the C++ runtime ran the prep, which CI guarantees via the
# build stage; a dropped phase counter fails here, not in a review
REQUIRED_NATIVE_STAGES = ("prep_candidates", "prep_select", "prep_routes")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="bench_smoke")
    parser.add_argument("--out", default=None,
                        help="also write the bench artifact JSON here "
                        "(consumed by the perf_gate CI stage)")
    args = parser.parse_args(argv)
    env = dict(
        os.environ,
        REPORTER_TPU_PLATFORM="cpu",  # never contend for the chip in CI
        BENCH_TRACES="48",
        BENCH_BASELINE_TRACES="8",
        BENCH_REPEATS="1",
        BENCH_BASELINE_REPEATS="1",
        BENCH_PALLAS="0",
    )
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, os.path.join(here, "bench.py")],
                          capture_output=True, text=True, timeout=900,
                          env=env, cwd=here)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:] + "\n")
        sys.stderr.write(f"bench smoke: bench.py rc={proc.returncode}\n")
        return 1
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if not lines:
        sys.stderr.write("bench smoke: no output\n")
        return 1
    try:
        art = json.loads(lines[-1])
    except json.JSONDecodeError as e:
        sys.stderr.write(f"bench smoke: artifact is not JSON: {e}\n")
        return 1
    missing = [k for k in REQUIRED_TOP if k not in art]
    stages = art.get("stages", {})
    missing += [f"stages.{k}" for k in REQUIRED_STAGES if k not in stages]
    try:
        from reporter_tpu import native
        native_ok = native.available()
    except Exception:
        native_ok = False
    if native_ok:
        missing += [f"stages.{k}" for k in REQUIRED_NATIVE_STAGES
                    if k not in stages]
    if missing:
        sys.stderr.write(f"bench smoke: missing keys: {missing}\n")
        return 1
    if not isinstance(stages["pipelined"], bool):
        sys.stderr.write("bench smoke: stages.pipelined must be a bool\n")
        return 1
    share = stages["prep_share"]
    # prep runs on the main thread, so its seconds are bounded by wall
    if not (isinstance(share, float) and 0.0 <= share <= 1.0):
        sys.stderr.write(
            f"bench smoke: stages.prep_share out of range: {share}\n")
        return 1
    r_share = stages["report_share"]
    if not (isinstance(r_share, float) and 0.0 <= r_share <= 1.0):
        sys.stderr.write(
            f"bench smoke: stages.report_share out of range: {r_share}\n")
        return 1
    # the wire-backend split (ISSUE 11): all three legs must time when
    # the C writer is available (CI's build stage guarantees it is).
    # Without the native toolchain there are no MatchRuns to serialise
    # (the numpy fallback returns plain dicts) and the split is None —
    # the smoke must keep passing on native-less boxes, like the
    # native-stage checks above
    writers = art.get("report_writers") or {}
    if native_ok:
        for k in ("python_s", "dict_s", "dict_vs_python", "native_s"):
            if not isinstance(writers.get(k), (int, float)):
                sys.stderr.write(
                    f"bench smoke: report_writers.{k} missing\n")
                return 1
    if not (art["value"] > 0 and art["vs_baseline"] > 0):
        sys.stderr.write("bench smoke: non-positive throughput\n")
        return 1
    # the serving-tier batched-query pair (ISSUE 14): pure numpy, no
    # native/device dependency — the ratio must always be measured
    # (parity is asserted inside the leg; perf_gate floors the ratio)
    query = art.get("query") or {}
    if not isinstance(query.get("batch_ratio"), (int, float)):
        sys.stderr.write(
            f"bench smoke: query.batch_ratio missing: {query}\n")
        return 1
    # the route-kernel triple (ISSUE 16): device relax vs host Dijkstra
    # vs native memo on identical pairs — the leg asserts byte-parity
    # BEFORE timing, so a measured ratio implies parity held; it needs
    # the native prep tensors, so native-less boxes see a skip record
    routes = art.get("routes") or {}
    if native_ok:
        if routes.get("parity") != "byte-identical" or \
                not isinstance(routes.get("device_vs_native"),
                               (int, float)):
            sys.stderr.write(
                f"bench smoke: routes leg broken: {routes}\n")
            return 1
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(art, f)
    print(f"bench smoke ok: {art['value']} traces/sec, "
          f"prep_share={share}, report_share={r_share}, "
          f"native_vs_python={writers.get('native_vs_python')}, "
          f"pipelined={stages['pipelined']}"
          + (f", artifact -> {args.out}" if args.out else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
