"""Pre-fork SO_REUSEPORT multi-process serving (service/prefork.py).

The end-to-end leg runs in a fresh interpreter (``subprocess``) rather
than forking this pytest process: the parent script forks its workers
BEFORE anything imports jax, which is exactly the discipline
service/server.py's ``main`` follows — everything heavyweight happens
post-fork inside ``make_service``.
"""
import json
import os
import signal
import subprocess
import sys

import pytest

from reporter_tpu.service import prefork


def test_writer_id_per_slot():
    """Each worker slot's writer identity is distinct (epoch tile names
    and ingest-ledger keys stay collision-free across workers) and
    composes with an inherited multihost tag."""
    assert prefork.writer_id_for_slot(0) == "p0"
    assert prefork.writer_id_for_slot(3) == "p3"
    assert prefork.writer_id_for_slot(2, "hostA") == "hostA.p2"
    ids = {prefork.writer_id_for_slot(i) for i in range(8)}
    assert len(ids) == 8


@pytest.mark.parametrize("env,pinned", [
    ({"REPORTER_TPU_PLATFORM": "cpu"}, True),
    ({"JAX_PLATFORMS": "cpu"}, True),
    ({"REPORTER_TPU_PLATFORM": "tpu"}, False),
    ({}, False),
])
def test_cpu_pinned_reads_env_only(monkeypatch, env, pinned):
    monkeypatch.delenv("REPORTER_TPU_PLATFORM", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert prefork.cpu_pinned() is pinned


def test_multi_proc_refused_off_cpu(monkeypatch, capsys):
    """One process per chip: procs > 1 without a CPU pin refuses before
    forking anything (no worker dies and restarts in a loop)."""
    monkeypatch.setenv("REPORTER_TPU_PLATFORM", "tpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(prefork.os, "fork",
                        lambda: pytest.fail("forked despite refusal"))
    built = []
    rc = prefork.serve_prefork(lambda: built.append(1), "127.0.0.1", 0, 2)
    assert rc == 2 and not built
    assert "one process per chip" in capsys.readouterr().err


def test_exit_code_decoding():
    """rc-137 awareness: a SIGKILLed worker and an ``os._exit(137)``
    crash failpoint decode to the same shell-style code."""
    pid = os.fork()
    if pid == 0:
        os._exit(137)
    _, status = os.waitpid(pid, 0)
    assert prefork._exit_code(status) == 137
    pid = os.fork()
    if pid == 0:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGKILL)
        os._exit(0)  # pragma: no cover - unreachable
    _, status = os.waitpid(pid, 0)
    assert prefork._exit_code(status) == 128 + signal.SIGKILL


_E2E_SCRIPT = r"""
import json, os, signal, socket, sys, threading, time, urllib.request

import numpy as np

from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.service.prefork import serve_prefork
from reporter_tpu.service.server import ReporterService
from reporter_tpu.synth import build_grid_city, generate_trace
from reporter_tpu.utils import metrics

city = build_grid_city(rows=8, cols=8, spacing_m=200.0, seed=3,
                       service_road_fraction=0.0, internal_fraction=0.0)
# a parent-process counter that must NOT leak into any worker's /metrics
metrics.count("prefork.test.sentinel", 9)

with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
base = f"http://127.0.0.1:{port}"


def make_service():
    return ReporterService(SegmentMatcher(net=city), threshold_sec=15,
                           max_batch=64, max_wait_ms=5.0)


def req_body(seed):
    rng = np.random.default_rng(seed)
    tr = None
    while tr is None:
        tr = generate_trace(city, f"veh-{seed}", rng, noise_m=3.0)
    return json.dumps(tr.request_json()).encode()


def call(path, body=None, timeout=120.0):
    r = urllib.request.Request(base + path, data=body,
                               method="POST" if body else "GET")
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.status, resp.headers.get("X-Reporter-Proc"), resp.read()


verdict = {"ok": False}


# warm the urlopen machinery (opener construction, lazy http imports)
# in the MAIN thread before any worker forks: a probe thread caught
# mid-import at fork time would leave the children deadlocked on the
# inherited per-module import locks (the quiet-parent fork discipline)
try:
    urllib.request.urlopen(base + "/stats", timeout=0.2)
except Exception:
    pass


def probe():
    # park through the fork window below for the same reason
    time.sleep(2.0)
    try:
        _probe()
    except Exception as e:
        verdict["err"] = f"{type(e).__name__}: {e}"


def _probe():
    deadline = time.time() + 180
    while True:
        try:
            call("/stats", timeout=5)
            break
        except Exception:
            if time.time() > deadline:
                verdict["err"] = "server never came up"
                return
            time.sleep(0.2)
    bodies = [req_body(i) for i in range(6)]
    # SO_REUSEPORT spreads fresh connections across both workers
    tags = {}
    for i in range(300):
        st, tag, _ = call("/report", bodies[i % len(bodies)])
        assert st == 200 and tag
        tags.setdefault(tag.split(":")[0], tag)
        if len(tags) == 2 and i >= 10:
            break
    if len(tags) < 2:
        verdict["err"] = f"one worker answered everything: {tags}"
        return
    # per-process /metrics: scrape until both workers answered one
    expos = {}
    for _ in range(300):
        st, tag, body = call("/metrics")
        expos.setdefault(tag.split(":")[0], body.decode())
        if len(expos) == 2:
            break
    for slot, text in expos.items():
        if "prefork_test_sentinel" in text:
            verdict["err"] = f"parent counters leaked into {slot}"
            return
        if "reporter_tpu_service_requests_total" not in text:
            verdict["err"] = f"{slot} reports no work of its own"
            return
    # SIGKILL p0 mid-load: the supervisor restarts it (rc 137 path);
    # no request may fail after ONE retry while the slot is down
    os.kill(int(tags["p0"].split(":")[1]), signal.SIGKILL)
    retried = 0
    for i in range(30):
        try:
            st, _t, _ = call("/report", bodies[i % len(bodies)])
        except Exception:
            retried += 1
            st, _t, _ = call("/report", bodies[i % len(bodies)])
        assert st == 200
        time.sleep(0.02)
    # the restarted slot answers under a NEW pid, same writer slot
    new_tag = None
    deadline = time.time() + 120
    while time.time() < deadline:
        _st, tag, _ = call("/stats", timeout=10)
        if tag and tag.startswith("p0:") and tag != tags["p0"]:
            new_tag = tag
            break
        time.sleep(0.1)
    verdict.update(ok=bool(new_tag), retried=retried,
                   tags=sorted(tags.values()), new_tag=new_tag)


t = threading.Thread(target=probe, daemon=True)
t.start()


def reaper():
    t.join()
    os.kill(os.getpid(), signal.SIGTERM)


threading.Thread(target=reaper, daemon=True).start()
rc = serve_prefork(make_service, "127.0.0.1", port, 2)
print("VERDICT:" + json.dumps(verdict))
sys.exit(0 if verdict.get("ok") and rc == 0 else 1)
"""


def test_prefork_two_workers_end_to_end():
    """Two SO_REUSEPORT workers behind one port: both answer, /metrics
    is per-process with no parent cross-talk, a SIGKILLed worker is
    restarted in its slot, and no request fails after one retry."""
    proc = subprocess.run(
        [sys.executable, "-c", _E2E_SCRIPT],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)
    tail = (proc.stdout + proc.stderr)[-2000:]
    assert proc.returncode == 0, tail
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("VERDICT:")]
    assert line, tail
    verdict = json.loads(line[-1][len("VERDICT:"):])
    assert verdict["ok"], verdict
    assert verdict["new_tag"] not in verdict["tags"]
