"""The served path's always-on stage timers: front door, dispatcher,
wire and collector timers, their thread CPU siblings, their profiler
annotations and the benchmark's readers over them."""
import gc
import glob
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.service.dispatch import BatchDispatcher
from reporter_tpu.service.server import ReporterService, make_server
from reporter_tpu.synth import build_grid_city, generate_trace
from reporter_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

#: stages timed with their thread CPU time
CPU_STAGES = ("service.headers", "service.parse", "service.columns",
              "report.serialise")
#: the per-layer readers this test feeds (benchmark/metrics/<name>.py)
READERS = ("front_ms_per_trace.closed", "queue_wait_ms_per_trace.closed",
           "dispatch_starved_share.closed", "handler_cpu_share.closed",
           "wire_ms_per_trace.closed", "gc_pause_ms_per_trace.closed")


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=10, cols=10, spacing_m=200.0, seed=5,
                           service_road_fraction=0.0,
                           internal_fraction=0.0)


@pytest.fixture(scope="module")
def server(city):
    service = ReporterService(SegmentMatcher(net=city), threshold_sec=15,
                              max_batch=64, max_wait_ms=5.0)
    httpd = make_server(service, "127.0.0.1", 0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()
    service.dispatcher.close()


def _requests(city, n, seed):
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        tr = generate_trace(city, f"timers-{seed}-{len(out)}", rng,
                            noise_m=3.0)
        if tr is not None:
            out.append(tr.request_json())
    return out


def _post_all(url, bodies):
    """POST each body to /report from its own thread; every answer 200."""
    codes = []

    def one(body):
        req = urllib.request.Request(
            f"{url}/report", data=json.dumps(body).encode(), method="POST")
        with urllib.request.urlopen(req) as r:
            r.read()
            codes.append(r.status)

    threads = [threading.Thread(target=one, args=(b,)) for b in bodies]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert codes == [200] * len(bodies)


def _window():
    snap = metrics.snapshot()
    return snap["counters"], {k: (v["count"], v["total_s"])
                              for k, v in snap["timers"].items()}


def _delta(before, after):
    (c0, t0), (c1, t1) = before, after
    counters = {k: v - c0.get(k, 0) for k, v in c1.items()}
    timers = {k: (v[0] - t0.get(k, (0, 0.0))[0],
                  v[1] - t0.get(k, (0, 0.0))[1]) for k, v in t1.items()}
    return counters, timers


def test_stage_timers_land_on_the_profilers_host_lines(city, server,
                                                      tmp_path):
    import jax
    from jax.profiler import ProfileData
    _post_all(server, _requests(city, 2, seed=1))  # warm the shapes
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # two rounds: the loop's wait between them opens and closes
        # inside the session
        _post_all(server, _requests(city, 3, seed=2))
        time.sleep(0.05)
        _post_all(server, _requests(city, 3, seed=3))
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    on_host = set()
    on_dispatch = set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            names = {ev.name for ev in line.events}
            on_host |= names
            if line.name.startswith("match-dispatch"):
                on_dispatch |= names
    for need in ("service.headers", "service.parse", "service.columns",
                 "report.serialise", "service.respond", "dispatch.idle",
                 "dispatch.fill", "dispatch.match_many", "matcher.prep",
                 "process.gc"):
        assert need in on_host, (need, sorted(on_host))
    for need in ("dispatch.idle", "dispatch.fill", "dispatch.match_many"):
        assert need in on_dispatch, (need, sorted(on_dispatch))
    # the matcher's lanes carry their own names, not the loop's
    assert "matcher.decode_wait" not in on_dispatch


def test_dispatch_timers_split_the_loop_threads_wall_time():
    def match_many(batch):
        time.sleep(0.004)
        return [{"n": i} for i in range(len(batch))]

    before = _window()
    t0 = time.perf_counter()
    d = BatchDispatcher(match_many, max_batch=8, max_wait_ms=5.0,
                        idle_grace_ms=1.0, name="timers-split")
    for burst in range(6):
        threads = [threading.Thread(target=d.submit, args=({"i": i},))
                   for i in range(11)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        time.sleep(0.05)
    assert d.close()
    wall = time.perf_counter() - t0
    counters, timers = _delta(before, _window())
    split = sum(timers[n][1] for n in ("dispatch.idle", "dispatch.fill",
                                       "dispatch.match_many"))
    assert abs(split - wall) <= 0.05 * wall, (split, wall)
    assert counters["dispatch.traces"] == 66
    assert timers["dispatch.queue_wait"][0] == counters["dispatch.traces"]


def test_thread_cpu_never_exceeds_wall(city, server):
    # every stage runs once a request: a sampled execution each
    _post_all(server, _requests(city, metrics.CPU_SAMPLE_EVERY, seed=3))
    timers = metrics.snapshot()["timers"]
    for stage in CPU_STAGES:
        wall, cpu = timers[stage], timers[stage + ".cpu"]
        sampled = timers[stage + ".cpu_wall"]
        assert 0 < cpu["count"] == sampled["count"] <= wall["count"], stage
        assert cpu["total_s"] <= sampled["total_s"] <= wall["total_s"], \
            stage


def test_cpu_timer_records_both_under_one_name_pair():
    r = metrics.Registry()  # a name's first execution is sampled
    with r.timer("stage", cpu=True):
        sum(range(20000))
    with r.timer("plain"):
        pass
    timers = r.snapshot()["timers"]
    assert set(timers) == {"stage", "stage.cpu", "stage.cpu_wall", "plain"}
    assert timers["stage.cpu"]["count"] == timers["stage"]["count"] == 1
    assert timers["stage.cpu_wall"]["total_s"] == timers["stage"]["total_s"]
    assert 0.0 < timers["stage.cpu"]["total_s"] <= timers["stage"]["total_s"]


def test_cpu_clock_is_read_on_one_execution_in_n():
    """The thread CPU clock is a system call: a stage reads it on the
    first of every ``CPU_SAMPLE_EVERY`` executions, counted per name."""
    r = metrics.Registry()
    every = metrics.CPU_SAMPLE_EVERY
    for _ in range(2 * every + 1):
        with r.timer("a", cpu=True):
            pass
        with r.timer("b", cpu=True):
            pass
    timers = r.snapshot()["timers"]
    for name in ("a", "b"):
        assert timers[name]["count"] == 2 * every + 1
        assert timers[name + ".cpu"]["count"] == 3
        assert timers[name + ".cpu_wall"]["count"] == 3


def test_a_forced_collection_is_a_timed_pause():
    metrics.install_gc_timer()
    metrics.install_gc_timer()  # idempotent: one hook
    assert gc.callbacks.count(metrics._on_gc) == 1
    before = metrics.snapshot()["timers"].get("process.gc.pause",
                                              {"count": 0, "total_s": 0.0})
    gc.collect()
    after = metrics.snapshot()["timers"]["process.gc.pause"]
    assert after["count"] >= before["count"] + 1
    assert after["total_s"] > before["total_s"]


def test_a_pause_inside_the_registry_lock_is_deferred_not_deadlocked():
    """A collection can start while this thread holds the registry lock;
    its observation waits for the next snapshot instead of the lock."""
    metrics.install_gc_timer()
    before = metrics.snapshot()["timers"].get("process.gc.pause",
                                              {"count": 0})["count"]
    with metrics.default._lock:
        gc.collect()
    assert metrics.snapshot()["timers"]["process.gc.pause"]["count"] \
        > before


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers_read_a_served_window(city, server, monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    from readings import Readings
    before = _window()
    # a sampled execution of every CPU-timed stage in the window
    _post_all(server, _requests(city, metrics.CPU_SAMPLE_EVERY, seed=4))
    counters, timers = _delta(before, _window())
    r = Readings(counters, timers, {}, None, "cpu")
    values = {name: _reader(name).read(r) for name in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    for share in ("dispatch_starved_share.closed",
                  "handler_cpu_share.closed"):
        assert 0.0 <= values[share] <= 100.0, values
    # a window with none of the timers (as a service without them
    # gives) reads nothing and raises nothing
    empty = Readings({"dispatch.traces": 5}, {}, {}, None, "cpu")
    assert all(_reader(n).read(empty) is None for n in READERS)


def test_metrics_module_imports_no_jax():
    code = ("import sys, reporter_tpu.utils.metrics as m\n"
            "with m.timer('x', cpu=True):\n"
            "    pass\n"
            "m.install_gc_timer()\n"
            "import gc; gc.collect()\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "False"


def test_device_trace_leaves_no_correlation_marker(tmp_path):
    """The profiled region carries the stage timers' own annotations;
    device_trace adds no span and no marker of its own."""
    from jax.profiler import ProfileData

    from reporter_tpu.obs import flightrec
    from reporter_tpu.obs import trace as obs_trace
    obs_trace.configure(True)
    try:
        flightrec.reset()
        with obs_trace.span("root"):
            with metrics.device_trace(str(tmp_path)):
                with metrics.timer("stage.traced"):
                    pass
        spans = {e["name"] for e in flightrec.events()}
    finally:
        obs_trace.configure(False)
        flightrec.reset()
    assert spans == {"root", "stage.traced"}
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "stage.traced" in names
    assert not any(n.startswith("reporter_tpu.trace:") for n in names)

