#!/usr/bin/env python
"""MULTICHIP harness: per-device-count decode throughput ratios.

The committed ``MULTICHIP_r0*`` artifacts recorded only ``{"n_devices":
8, "ok": true}`` — a liveness verdict with no measurement, which is why
ROADMAP item 1 calls reviving this harness "the measurement half" of
the mesh scale-out work. This tool runs the SAME serialized decode leg
at several device counts (``REPORTER_TPU_VIRTUAL_DEVICES`` on the CPU
backend; ``chip_smoke.py --chips 4`` covers real chips) in
bounded subprocesses and emits one artifact whose throughput RATIOS
(count N over count 1, same box, same leg — the only number that
survives box drift) are parsed by ``obs/ledger.py`` and gated by
``tools/perf_gate.py --multichip``.

Artifact shape (a superset of the legacy verdict keys, so old ledger
seeding still reads it):

    {"n_devices": <max count>, "rc": 0, "ok": true, "skipped": false,
     "tail": "", "legs": [{"n_devices": N, "traces_per_sec": T,
     "rc": 0}, ...], "ratios": {"2": r2, "4": r4, ...}}

On a CPU box the virtual-device mesh shards a compute-bound decode
over the SAME cores, so ratios hover near (or below) 1.0 — the harness
measures, the gate's floor (default 0.5) only catches a catastrophic
sharding regression. On real multi-chip hardware the same artifact
carries the real scaling curve.

Usage:
    python tools/multichip_bench.py [--devices 1,2,4,8] [--traces 96]
        [--out MULTICHIP_rNN.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LEG_CODE = r"""
import json, time
# THE r06 BUG: nothing in this child ever applied the forced host-
# device count, so every leg came up on ONE device and the "2/4/8
# device" ratios measured chunk-size noise (devices_seen: 1 in every
# committed r06 leg). ensure_backend honours REPORTER_TPU_PLATFORM /
# REPORTER_TPU_VIRTUAL_DEVICES BEFORE the first backend resolution —
# it must run before anything imports a jax-touching module.
from reporter_tpu.utils.runtime import ensure_backend
ensure_backend()
import jax
import numpy as np
from reporter_tpu.core.tracebatch import TraceBatch
from reporter_tpu.matcher import MatchParams, SegmentMatcher
from reporter_tpu.synth import build_grid_city, generate_trace

n_traces = {n_traces}
city = build_grid_city(rows=12, cols=12, spacing_m=200.0, seed=42)
matcher = SegmentMatcher(net=city, params=MatchParams(max_candidates=8))
rng = np.random.default_rng(7)
reqs = []
while len(reqs) < n_traces:
    tr = generate_trace(city, f"v{{len(reqs)}}", rng, noise_m=4.0,
                        min_route_edges=5, max_route_edges=60)
    if tr is not None:
        reqs.append(tr.request_json())
tb = TraceBatch.from_requests(reqs)
tb.options = reqs[0]["match_options"]
matcher.match_many(reqs[:8])  # compile the bucket shapes
best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    matcher.match_many(tb)
    best = min(best, time.perf_counter() - t0)
from reporter_tpu.ops import decode_mesh_size
print("LEG:" + json.dumps({{
    "devices_seen": len(jax.devices()),
    "mesh_data": decode_mesh_size(),
    "traces_per_sec": round(n_traces / best, 1)}}))
"""


def run_leg(n_devices: int, n_traces: int, timeout_s: float) -> dict:
    env = dict(os.environ,
               REPORTER_TPU_PLATFORM=os.environ.get(
                   "REPORTER_TPU_PLATFORM", "cpu"),
               REPORTER_TPU_VIRTUAL_DEVICES=str(n_devices),
               REPORTER_TPU_SHARD="1",
               REPORTER_TPU_PIPELINE="0")
    # a leg measures ITS device count, not an inherited slice
    env.pop("REPORTER_TPU_DEVICE_SLICE", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _LEG_CODE.format(n_traces=n_traces)],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=HERE)
    except subprocess.TimeoutExpired:
        return {"n_devices": n_devices, "rc": 124,
                "traces_per_sec": None, "tail": "leg timed out"}
    leg = {"n_devices": n_devices, "rc": proc.returncode,
           "traces_per_sec": None, "tail": ""}
    for line in proc.stdout.splitlines():
        if line.startswith("LEG:"):
            parsed = json.loads(line[len("LEG:"):])
            leg["traces_per_sec"] = parsed["traces_per_sec"]
            leg["devices_seen"] = parsed["devices_seen"]
            leg["mesh_data"] = parsed["mesh_data"]
    if proc.returncode != 0 or leg["traces_per_sec"] is None:
        leg["tail"] = (proc.stderr.strip().splitlines() or ["?"])[-1][:200]
    # the r06 lesson, enforced: a leg that did not actually SEE its
    # requested device count is a failed leg, not a slow one — its
    # throughput would silently become a bogus ratio denominator/
    # numerator. (devices_seen is leg-asserted; perf_gate --multichip
    # re-checks the committed artifact.)
    if leg["rc"] == 0 and leg.get("devices_seen") != n_devices:
        leg["rc"] = 5
        leg["tail"] = (f"devices_seen={leg.get('devices_seen')} != "
                       f"requested {n_devices}: the forced host-device "
                       "count never reached the leg")
    return leg


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="multichip_bench",
                                     description=__doc__.splitlines()[0])
    parser.add_argument("--devices", default="1,2,4,8",
                        help="comma-separated device counts (default "
                        "1,2,4,8; count 1 is the ratio denominator and "
                        "is always added)")
    parser.add_argument("--traces", type=int, default=96)
    parser.add_argument("--timeout", type=float, default=600.0,
                        help="per-leg subprocess timeout (seconds)")
    parser.add_argument("--out", default=None,
                        help="write the artifact here (default: stdout "
                        "only)")
    args = parser.parse_args(argv)
    counts = sorted({int(c) for c in args.devices.split(",") if c}
                    | {1})

    legs = [run_leg(n, args.traces, args.timeout) for n in counts]
    base = next((leg["traces_per_sec"] for leg in legs
                 if leg["n_devices"] == 1 and leg["traces_per_sec"]),
                None)
    ratios = {}
    if base:
        for leg in legs:
            if leg["n_devices"] != 1 and leg["traces_per_sec"]:
                ratios[str(leg["n_devices"])] = round(
                    leg["traces_per_sec"] / base, 3)
    ok = all(leg["rc"] == 0 and leg["traces_per_sec"] for leg in legs)
    art = {
        # legacy verdict keys (obs/ledger.py seeded these shapes)
        "n_devices": max(counts), "rc": 0 if ok else 1, "ok": ok,
        "skipped": False,
        "tail": "" if ok else "; ".join(
            f"n={leg['n_devices']}: rc={leg['rc']} {leg['tail']}"
            for leg in legs if leg["rc"] != 0),
        # the measurement half (ROADMAP item 1)
        "legs": legs,
        "ratios": ratios,
    }
    body = json.dumps(art, indent=1)
    print(body)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(body)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
