#!/usr/bin/env python
"""Pre-fork serving throughput: N processes vs 1 on the bigreplay mix.

Measures sustained HTTP `/report` request throughput of the service in
single-process mode vs pre-fork ``SO_REUSEPORT`` multi-process mode
(ISSUE 11 acceptance: 2 processes >= 1.6x one process on the bigreplay
topology), using tools/bigreplay.py's city profiles for the request
mix. Each mode runs in a fresh interpreter (the parent must fork its
workers before anything imports jax), takes load from concurrent
client threads against warm workers (every worker has answered
requests before the timed window), and reports requests/sec.

Prints ONE JSON line:
    {"kind": "prefork_bench", "procs": N, "clients": C,
     "duration_s": D, "single_rps": R1, "multi_rps": RN,
     "ratio": RN/R1}

Usage:
    python tools/prefork_bench.py [--procs 2] [--clients 8]
        [--duration 10] [--min-ratio 0] [--out FILE]

``--min-ratio R`` gates the run (exit 1 below R) — the bench box
acceptance leg; CI boxes with one core cannot express the win, so the
default does not gate.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MODE_SCRIPT = r"""
import json, os, signal, socket, sys, threading, time, urllib.request

import numpy as np

from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.service.prefork import serve_prefork
from reporter_tpu.service.server import ReporterService
from reporter_tpu.synth import build_grid_city, generate_trace
from tools.bigreplay import CITY_PROFILES

PROCS = {procs}
CLIENTS = {clients}
DURATION = {duration}

# the bigreplay urban-canyon profile: densest graph, noisiest probes
name, grid_kw, noise_m, period_s, _queue = CITY_PROFILES[0]
city = build_grid_city(service_road_fraction=0.0, internal_fraction=0.0,
                       **grid_kw)
rng = np.random.default_rng(1234)
bodies = []
while len(bodies) < 48:
    tr = generate_trace(city, f"bench-{{len(bodies)}}", rng,
                        noise_m=noise_m, sample_period_s=period_s,
                        min_route_edges=8)
    if tr is not None:
        bodies.append(json.dumps(tr.request_json()).encode())

with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
base = f"http://127.0.0.1:{{port}}"


def make_service():
    return ReporterService(SegmentMatcher(net=city), threshold_sec=15,
                           max_batch=64, max_wait_ms=5.0)


def post(body, timeout=120.0):
    r = urllib.request.Request(base + "/report", data=body,
                               method="POST")
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.status, resp.headers.get("X-Reporter-Proc")


result = {{}}


def drive():
    time.sleep(2.0)  # quiet-parent fork window
    try:
        _drive()
    except Exception as e:
        result["err"] = f"{{type(e).__name__}}: {{e}}"


def _drive():
    deadline = time.time() + 240
    while True:
        try:
            post(bodies[0])
            break
        except Exception:
            if time.time() > deadline:
                result["err"] = "service never came up"
                return
            time.sleep(0.3)
    # warm every worker: keep firing until each proc tag has answered
    # enough to have compiled its decode shapes
    seen = {{}}
    for i in range(600):
        _st, tag = post(bodies[i % len(bodies)])
        slot = tag.split(":")[0]
        seen[slot] = seen.get(slot, 0) + 1
        if len(seen) >= PROCS and min(seen.values()) >= 24:
            break
    # timed window: CLIENTS threads firing as fast as the service
    # answers; count successes only (a refused connection mid-run
    # would be a worker death — none expected here)
    stop = time.time() + DURATION
    counts = [0] * CLIENTS

    def client(ci):
        i = ci
        while time.time() < stop:
            st, _tag = post(bodies[i % len(bodies)])
            if st == 200:
                counts[ci] += 1
            i += CLIENTS

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(CLIENTS)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    result.update(rps=round(sum(counts) / wall, 1), workers=len(seen))


t = threading.Thread(target=drive, daemon=True)
try:
    urllib.request.urlopen(base + "/stats", timeout=0.2)
except Exception:
    pass  # warm the opener machinery pre-fork, in the main thread
t.start()


def reaper():
    t.join()
    os.kill(os.getpid(), signal.SIGTERM)


threading.Thread(target=reaper, daemon=True).start()
rc = serve_prefork(make_service, "127.0.0.1", port, PROCS)
print("MODE:" + json.dumps(result))
sys.exit(0 if result.get("rps") else 1)
"""


def run_mode(procs: int, clients: int, duration: float) -> dict:
    script = _MODE_SCRIPT.format(procs=procs, clients=clients,
                                 duration=duration)
    env = dict(os.environ)
    if procs > 1:
        # process-per-core deployment config: each worker keeps its
        # intra-op parallelism to itself instead of N workers' XLA /
        # BLAS / prep pools all fighting for every core (without this
        # the multi-process leg measures thread thrash, not scaling)
        per = max(1, (os.cpu_count() or procs) // procs)
        env.update(REPORTER_TPU_PREP_THREADS=str(per),
                   OMP_NUM_THREADS=str(per),
                   OPENBLAS_NUM_THREADS=str(per),
                   XLA_FLAGS=(env.get("XLA_FLAGS", "") +
                              " --xla_cpu_multi_thread_eigen=false"
                              ).strip())
    # pre-fork serving is CPU-only (service/prefork.py): pin it
    env.setdefault("REPORTER_TPU_PLATFORM", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          capture_output=True, text=True, timeout=900,
                          env=env)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("MODE:")]
    if proc.returncode != 0 or not lines:
        raise SystemExit(f"procs={procs} leg failed rc={proc.returncode}"
                         f": {(proc.stdout + proc.stderr)[-2000:]}")
    return json.loads(lines[-1][len("MODE:"):])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="prefork_bench",
                                     description=__doc__.splitlines()[0])
    parser.add_argument("--procs", type=int, default=2)
    parser.add_argument("--clients", type=int, default=8)
    parser.add_argument("--duration", type=float, default=10.0)
    parser.add_argument("--min-ratio", type=float, default=0.0,
                        help="fail below this multi/single ratio "
                        "(bench-box acceptance: 1.6; default no gate)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    single = run_mode(1, args.clients, args.duration)
    multi = run_mode(args.procs, args.clients, args.duration)
    ratio = round(multi["rps"] / single["rps"], 3) if single["rps"] \
        else None
    art = {"kind": "prefork_bench", "procs": args.procs,
           "clients": args.clients, "duration_s": args.duration,
           "single_rps": single["rps"], "multi_rps": multi["rps"],
           "ratio": ratio}
    body = json.dumps(art, separators=(",", ":"))
    print(body)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(body)
    if args.min_ratio and (ratio is None or ratio < args.min_ratio):
        sys.stderr.write(f"prefork_bench: FAIL: ratio {ratio} < floor "
                         f"{args.min_ratio}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
