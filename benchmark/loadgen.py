"""The load generator: a child process of ``run.py`` that never imports JAX.

It reads one JSON job from stdin, then talks to its parent in JSON lines
(stdout to the parent, stdin from it):

1. builds the cell's graph from the seed and writes it under the cache
   directory -> ``{"graph": path}``;
2. builds the warm-up's requests -> ``{"warm": path}``, then the rest of
   the request pool while the parent warms up -> ``{"pool": ...}``;
3. on ``{"go": port}`` runs the traffic's driver for the window ->
   ``{"window_closed": ...}`` the moment the window closes;
4. on ``{"check": true}`` (sent once the parent has read the device's
   memory and stopped serving) compares a sample of the answers, drawn
   from the seed, with the plain reference -> ``{"result": ...}``.
"""
from __future__ import annotations

import gc
import importlib
import json
import multiprocessing
import os
import signal
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import gen  # noqa: E402
from check import Tally  # noqa: E402

_net = None
_ref = None


def say(**msg) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


def hear() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("parent went away")
    return json.loads(line)


#: the levels every request asks for (the reference's /report defaults)
LEVELS = {"report_levels": [0, 1], "transition_levels": [0, 1]}


def _chunk(args) -> list:
    config, seed, lo, hi = args
    out = []
    for i in range(lo, hi):
        tr = gen.vehicle(_net, config, seed, i)
        if tr is not None:
            body = json.dumps(tr.request_json(**LEVELS),
                separators=(",", ":")).encode()
            out.append((i, body, len(tr.points)))
    return out


#: the warm-up's vehicles are drawn from indices of their own, so that
#: no request of the window was served in the warm-up
WARM_FIRST = 1 << 40


def build_pool(job: dict, size: int, pool, first: int = 0) -> list:
    """``size`` qualifying requests as (index, body bytes, points), in
    index order from ``first``: the same pool whatever the number of
    processes."""
    config, seed = job["config"], job["seed"]
    step = 64
    out = []
    lo = first
    while len(out) < size:
        spans = [(config, seed, a, a + step)
                 for a in range(lo, lo + step * job["procs"] * 4, step)]
        lo = spans[-1][3]
        for part in pool.map(_chunk, spans):
            out.extend(part)
    return out[:size]


def _check_one(args):
    req, served = args
    expected = _ref.report(req)
    got = None
    if served is not None:
        try:
            got = json.loads(served)
        except ValueError:
            got = None
    return got, expected


def _control_one(req):
    """The control's answer: the reference one step down in precision,
    an 8-bit float wire and bfloat16 scores (``ref/control.py``)."""
    from ref.control import viterbi_decode_bf16
    saved = _ref.decode, _ref.wire
    _ref.decode, _ref.wire = viterbi_decode_bf16, "fp8"
    try:
        return _ref.report(req)
    finally:
        _ref.decode, _ref.wire = saved


def main() -> int:
    global _net, _ref
    # a parent that gives up terminates this process: exit through the
    # finally below, which stops the workers
    signal.signal(signal.SIGTERM, lambda *_a: sys.exit(1))
    job = json.loads(sys.stdin.readline())
    config, traffic, seed = job["config"], job["traffic"], job["seed"]
    seconds = job["seconds"]
    cache = job["cache_dir"]
    os.makedirs(cache, exist_ok=True)
    t0 = time.perf_counter()
    _net = gen.build_graph(config, seed)
    graph = os.path.join(cache, f"graph-{os.getpid()}.npz")
    _net.save(graph)
    say(graph=graph, graph_s=time.perf_counter() - t0)
    # the reference's index, built before the workers fork so that each
    # inherits it
    from ref.pipeline import Reference
    _ref = Reference(_net, config["matcher"],
                     config["service"]["threshold_sec"])

    size = max(int(traffic["pool_per_s"] * seconds), job["pool_min"])
    # forked before any thread starts, so that each worker inherits the
    # graph and the reference's index instead of unpickling them
    ctx = multiprocessing.get_context("fork")
    workers = ctx.Pool(job["procs"])
    try:
        t1 = time.perf_counter()
        # the warm-up's requests first, so the parent warms while the
        # rest of the pool is built
        warm_n = job["warm_traces"]
        pool = build_pool(job, warm_n, workers, first=WARM_FIRST)
        warm = os.path.join(cache, f"warm-{os.getpid()}.json")
        with open(warm, "wb") as f:
            f.write(b"[" + b",".join(p[1] for p in pool) + b"]")
        say(warm=warm)
        pool = build_pool(job, size, workers)
        bodies = [p[1] for p in pool]
        points = [p[2] for p in pool]
        say(pool=len(bodies), pool_s=time.perf_counter() - t1,
            points_min=min(points), points_median=int(
                statistics.median(points)), points_max=max(points))

        # the pool and the reference's index stay alive all run: keep the
        # collector off them, so that no pass over them pauses the
        # generator's threads in the window
        gc.freeze()
        driver = importlib.import_module(f"drivers.{traffic['driver']}")
        port = hear()["go"]
        out = driver.drive(port, bodies, traffic, seconds, seed,
                           lambda: say(window_closed=True))
        records = out["records"]
        repeats = max(0, max((r[0] for r in records), default=-1) + 1
                      - len(bodies))
        # a backlog that grows through the window shows as latency that
        # grows from its first third to its last
        thirds = [sorted(1e3 * (r[2] - r[1]) for r in records
                         if k * seconds / 3 <= r[1] < (k + 1) * seconds / 3)
                  for k in range(3)]
        e2e = driver.end_to_end(out, seconds)
        say(window=dict(
            attempted=len(out["attempted_idx"]), answered=len(records),
            ok=sum(r[3] == 200 for r in records),
            ok_in_window=sum(r[3] == 200 and r[2] <= seconds
                             for r in records),
            repeats=repeats, pool=len(bodies),
            latency_p50_ms_by_third=[t[len(t) // 2] if t else None
                                     for t in thirds],
            **out.get("notes", {})))

        hear()  # {"check": true}: the program's state is freed
        t2 = time.perf_counter()
        served = {i: body if status == 200 else None
                  for i, _s, _d, status, body in records}
        due = out["attempted_idx"]
        rng = np.random.default_rng([seed % (2 ** 63), 0xC4EC])
        n = min(job["sample"], len(due))
        pick = sorted(rng.choice(len(due), size=n, replace=False).tolist())
        sample = [(json.loads(bodies[due[k] % len(bodies)]),
                   served.get(due[k])) for k in pick]
        pairs = workers.map(_check_one, sample, chunksize=4)
        if job.get("control"):
            # the control's answer stands in the served body's place, so
            # that the run has to read not correct
            ctl = workers.map(_control_one, [s[0] for s in sample],
                              chunksize=4)
            pairs = [(c, want) for c, (_got, want) in zip(ctl, pairs)]
        tally = Tally()
        for got, expected in pairs:
            tally.add(got, expected)
        numbers = tally.numbers()
        numbers["check_s"] = time.perf_counter() - t2
        numbers["max_points"] = max((len(s[0]["trace"]) for s in sample),
                                    default=0)
        if "jax" in sys.modules:
            raise SystemExit("the load generator imported JAX")
        say(result=dict(end_to_end=e2e, check=numbers,
                        attempted=len(due),
                        failed=sum(served.get(i) is None for i in due)))
    finally:
        workers.terminate()
        workers.join()
        for path in (graph, os.path.join(cache, f"warm-{os.getpid()}.json")):
            if os.path.exists(path):
                os.remove(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
