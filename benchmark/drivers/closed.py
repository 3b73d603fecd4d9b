"""Closed loop: C callers over keep-alive HTTP, each waiting for its reply
before it sends the next request (backfill processes, Kafka workers).

Traffic parameters: ``clients``. End-to-end metric: ``traces_per_s``,
the traces answered 200 within the window over the window's seconds.
The parent serves ``/report`` over HTTP (``serve_http``).
"""
from __future__ import annotations

import threading
import time

from drivers import http_client


def serve(*args, **kw) -> dict:
    """The parent's side of the cell: ``serve_http.serve``."""
    from serve_http import serve as serve_http
    return serve_http(*args, **kw)


def drive(port: int, bodies: list, params: dict, seconds: float, seed: int,
          on_close) -> dict:
    """Send until the window closes; returns the records of every request
    sent in the window: (index into bodies, sent, done, status, body),
    times in seconds from the window's start. (A driver may add
    ``notes``, a dict the load generator logs with the window.)"""
    lock = threading.Lock()
    nxt = [0]
    attempted = []
    records = []
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def caller() -> None:
        conn = http_client.Connection(port)
        try:
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                sent = time.perf_counter()
                if sent >= t_end:
                    return
                with lock:
                    attempted.append(i)
                status, body = conn.post(bodies[i % len(bodies)])
                done = time.perf_counter()
                with lock:
                    records.append((i, sent - t0, done - t0, status, body))
        finally:
            conn.close()

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(params["clients"])]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t_end - time.perf_counter()))
    on_close()
    for t in threads:
        t.join(timeout=seconds + 120.0)
    return {"records": records, "attempted_idx": attempted}


def end_to_end(out: dict, seconds: float) -> dict:
    ok = sum(1 for _i, _s, done, status, _b in out["records"]
             if status == 200 and done <= seconds)
    return {"traces_per_s": {"value": ok / seconds, "unit": "traces/s"}}
