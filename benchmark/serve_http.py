"""The parent's side of a cell whose driver sends HTTP to ``/report``:
the service as ``python -m reporter_tpu serve`` builds it
(``make_service``, ``make_server``) on a local port, warmed at every
decode shape the configuration can form, loaded for the window by the
load generator's process (``loadgen.py``), which never imports JAX and
runs the traffic's driver (``drive``, ``end_to_end``) and the check.

A driver of this kind names :func:`serve` as its own (``drivers/closed.py``).
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import threading
import time

from harness import (CACHE, HERE, Failure, Window, check_zero, delta, log,
                     snapshot)

#: the load generator's worker processes, and the least pool it builds
PROCS = 8
POOL_MIN = 2048
#: a rehearsal's, on the CPU beside the tests
REHEARSE_PROCS = 2
REHEARSE_POOL_MIN = 256


class LayerSpans:
    """Host spans around the calls into each layer, for the traced run:
    ``jax.profiler.TraceAnnotation`` on the profiler's clock, so that the
    trace's idle gaps can be named by what the host was doing
    (``devtrace``). Recorded from the benchmark's side of each call;
    spans inside the program are a later change."""

    #: (module, attribute, span): the entry points wrapped, outermost
    #: first; the dispatcher's batch call is wrapped on the service's
    #: own dispatcher, which holds it as a bound method
    POINTS = (
        ("reporter_tpu.service.server", "ReporterService.handle",
         "bench.handle"),
        ("reporter_tpu.matcher.matcher", "prepare_batch", "bench.prep"),
        ("reporter_tpu.matcher.matcher", "SegmentMatcher._drain_stage",
         "bench.drain_assemble"),
        ("reporter_tpu.service.server", "report_wire", "bench.wire"),
    )

    def __init__(self, service):
        self.saved = []
        for mod_name, attr, span in self.POINTS:
            owner = importlib.import_module(mod_name)
            *path, name = attr.split(".")
            for part in path:
                owner = getattr(owner, part)
            self._wrap(owner, name, span)
        self._wrap(service.dispatcher, "_match_many", "bench.batch")

    def _wrap(self, owner, name: str, span: str) -> None:
        import functools

        import jax
        real = getattr(owner, name)

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(span):
                return real(*a, **kw)

        setattr(owner, name, functools.wraps(real)(wrapped))
        self.saved.append((owner, name, real))

    def close(self) -> None:
        for owner, name, real in reversed(self.saved):
            setattr(owner, name, real)


def warm_shapes(service, reqs: list, points_max: int) -> int:
    """Build every (rows, T) decode shape the window can meet: each
    power-of-two T from the ladder's first bucket up to the bucket of the
    longest trace the configuration keeps (the matcher splits a wasteful
    group into power-of-two sub-buckets), times each power-of-two batch
    up to the dispatcher's cap. The ladder is pinned to the one T with
    splitting off meanwhile, so every batch decodes at that T (a longer
    trace is cut to it). Returns the batches sent."""
    from reporter_tpu.matcher.batchpad import ENV_BUCKETS, bucket_ladder
    ladder = bucket_ladder()[0]
    top = next((b for b in ladder if b >= points_max), ladder[-1])
    sent = 0
    saved = os.environ.get(ENV_BUCKETS)
    try:
        T = ladder[0]
        while T <= top:
            os.environ[ENV_BUCKETS] = f"{T}@off"
            r = 1
            while r <= min(service.dispatcher.max_batch, len(reqs)):
                got = service.report_many(reqs[:r])
                if any(g is None for g in got):
                    raise Failure(f"warm batch ({r}, {T}): "
                                  f"{sum(g is None for g in got)} failed")
                sent += 1
                r *= 2
            T *= 2
    finally:
        if saved is None:
            os.environ.pop(ENV_BUCKETS)
        else:
            os.environ[ENV_BUCKETS] = saved
    return sent


class Child:
    """The load generator's process (``loadgen.py``): JSON lines both
    ways."""

    def __init__(self, job: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith(("TPU_", "JAX_", "XLA_"))})
        self.send(job)

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def expect(self, key: str):
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise Failure(f"load generator exited (rc "
                              f"{self.proc.wait()}) before {key!r}")
            msg = json.loads(line)
            if key in msg:
                return msg
            log("loadgen", **msg)

    def stop(self) -> None:
        """End the child and its workers, and wait for them: its stdin
        closed, it exits through its clean-up; a child that does not is
        terminated, then killed."""
        self.proc.stdin.close()
        for end in (None, self.proc.terminate, self.proc.kill):
            if end is not None:
                end()
            try:
                self.proc.wait(timeout=30)
                return
            except subprocess.TimeoutExpired:
                pass


def serve(cell, args, clock, t_start: float, trace_dir) -> dict:
    conf_svc = cell.config["service"]
    os.environ["THRESHOLD_SEC"] = str(conf_svc["threshold_sec"])
    os.environ["MATCH_BATCH_MAX"] = str(conf_svc["match_batch_max"])
    os.environ["MATCH_BATCH_WAIT_MS"] = str(conf_svc["match_batch_wait_ms"])
    child = Child({
        "config": cell.config, "traffic": cell.traffic,
        "seed": args.seed, "seconds": args.seconds, "cache_dir": CACHE,
        "procs": REHEARSE_PROCS if cell.rehearse else PROCS,
        "pool_min": REHEARSE_POOL_MIN if cell.rehearse else POOL_MIN,
        "warm_traces": conf_svc["match_batch_max"],
        "sample": cell.config["check"]["sample"],
        "control": bool(args.control)})
    try:
        return _serve(cell, args, clock, t_start, trace_dir, child)
    finally:
        child.stop()


def _serve(cell, args, clock, t_start, trace_dir, child) -> dict:
    from reporter_tpu import native
    from reporter_tpu.matcher import Configure
    from reporter_tpu.service.server import make_server, make_service

    graph = child.expect("graph")["graph"]
    conf = {"graph": graph, "matcher": cell.config["matcher"]}
    Configure(conf)
    service = make_service(conf)
    if not native.available():
        raise Failure("native host runtime did not load")
    with open(child.expect("warm")["warm"]) as f:
        reqs = json.load(f)
    start = snapshot()
    t0 = time.perf_counter()
    warm_batches = warm_shapes(service, reqs,
                               cell.config["probes"]["points"][1])
    warm_s = time.perf_counter() - t0
    del reqs
    check_zero("warm-up", delta(start, snapshot())[0])
    httpd = make_server(service, "127.0.0.1", 0)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    spans = LayerSpans(service) if trace_dir else None
    window = Window(clock, trace_dir, args.fault)
    try:
        with window:
            child.send({"go": httpd.server_address[1]})
            child.expect("window_closed")
        seen = child.expect("window")["window"]
    finally:
        if spans is not None:
            spans.close()
        httpd.shutdown()
        httpd.server_close()
        service.dispatcher.close()
    window.finish(start, warm_batches=warm_batches, warm_s=warm_s, **seen)
    child.send({"check": True})
    return window.result(t_start, **child.expect("result")["result"])
