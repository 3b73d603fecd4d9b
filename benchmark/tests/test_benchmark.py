"""The benchmark's own tests, on the CPU:

- the trace reduction, on synthetic planes and on a small trace recorded
  on the chip (``fixtures/tpu_decode.xplane.pb``, ``record_fixture.py``);
- the roofline's count is the same for ``scan``, ``assoc`` and
  ``pallas`` on the same shapes, and an unknown ``device_kind`` raises;
- a new configuration, traffic mix, driver, kernel and metric are found
  from files and entries alone;
- a CPU rehearsal of every cell, and the refusals: no chip, the
  benchmark alone in a directory;
- the check fails a run whose timed path is broken (``--fault``), and
  fails the control (an 8-bit float wire and bfloat16 scores) put in
  the program's place.

    python3 -m pytest benchmark/tests -q
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never the chip

import devtrace  # noqa: E402
import roofline  # noqa: E402
from peaks import peaks  # noqa: E402

DECODE = roofline.kernels()["decode"]

FIXTURE = os.path.join(HERE, "fixtures", "tpu_decode.xplane.pb")
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=evs) for ln, evs in lines.items()])


def test_reduction_of_synthetic_planes():
    dev = _plane("/device:TPU:0", {
        devtrace.OPS_LINE: [_ev("a", 100, 100), _ev("b", 150, 100),
                            _ev("c", 600, 100)],
        devtrace.MODULES_LINE: [_ev("jit_viterbi_assoc_batch(7)", 100, 150),
                                _ev("jit_viterbi_assoc_batch(7)", 600, 100),
                                _ev("jit_pad(3)", 250, 0)]})
    host = _plane("/host:CPU", {"python": [_ev("bench.prep", 260, 300),
                                           _ev("bench.wire", 300, 50),
                                           _ev("Transpose", 0, 1000)]})
    s = devtrace.Summary([dev, host], window=(0, 1000))
    assert s.busy_s == pytest.approx(250e-9)   # [100, 250) + [600, 700)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.idle_share == pytest.approx(0.75)
    assert s.modules["jit_viterbi_assoc_batch"] == pytest.approx(250e-9)
    assert s.module_calls["jit_viterbi_assoc_batch"] == 2
    gaps = s.breakdown()["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([350e-9, 300e-9, 100e-9])
    # the layer spans open at each gap's midpoint
    assert [g[0] for g in gaps] == ["bench.prep", "no layer span",
                                    "no layer span"]


def test_reduction_of_a_recorded_chip_trace():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded chip trace")
    s = devtrace.summarise(FIXTURE)
    assert s.devices and s.devices[0].startswith("/device:TPU")
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert s.module_seconds(r"viterbi") > 0
    assert s.module_calls[next(k for k in s.modules if "viterbi" in k)] >= 3
    top = s.breakdown()
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_roofline_count_is_the_same_for_every_backend(monkeypatch):
    """The spy records each call's (B, T, K, wire) whatever backend
    decodes it, and the work is a function of those alone."""
    sys.path.insert(0, ROOT)
    os.environ.setdefault("REPORTER_TPU_PLATFORM", "cpu")
    from reporter_tpu import ops
    from reporter_tpu.matcher.hmm import NORMAL, RESTART
    import harness
    rng = np.random.default_rng(1)
    B, T, K = 4, 16, 8
    args = (rng.uniform(0, 50, (B, T, K)).astype(np.float16),
            np.ones((B, T, K), bool),
            rng.uniform(0, 90, (B, T, K, K)).astype(np.float16),
            rng.uniform(0, 30, (B, T)).astype(np.float16),
            np.where(np.arange(T) == 0, RESTART, NORMAL).astype(
                np.int32)[None].repeat(B, 0),
            np.float32(4.07), np.float32(3.0))
    seen = {}
    for backend in ("scan", "assoc", "pallas"):
        monkeypatch.setenv("REPORTER_TPU_DECODE", backend)
        spy = harness.CallSpy()
        try:
            spy.on = True
            paths = np.asarray(ops.decode_batch(*args)[0])
        finally:
            spy.close()
        (key, n), = spy.calls.items()
        seen[backend] = (DECODE.work(*key[1:]), paths)
    works = {b: w for b, (w, _p) in seen.items()}
    assert len(set(works.values())) == 1, works
    ops_, nbytes = works["scan"]
    assert ops_ == 2 * B * (T - 1) * K * K
    for b in ("assoc", "pallas"):
        np.testing.assert_array_equal(seen[b][1], seen["scan"][1])


def test_share_cannot_pass_100_by_construction():
    """The least time is the larger of the two bounds at the peaks: a
    device time that covers the work can only read at most 100%."""
    ops_, nbytes = DECODE.work(128, 256, 8, np.float16)
    t, bound = roofline.min_seconds(ops_, nbytes, "TPU v5 lite")
    p = peaks("TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / p["bytes_per_s"])
    assert t >= ops_ / p["flops_per_s"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.min_seconds(1.0, 1.0, "cpu")


TOY_DRIVER = '''"""A driver whose parent calls a toy kernel in its window."""
import harness


def square(x):
    return x * x


def serve(cell, args, clock, t_start, trace_dir):
    import sys
    me = sys.modules[__name__]
    start = harness.snapshot()
    window = harness.Window(clock, trace_dir)
    with window:
        for n in range(cell.traffic["calls"]):
            me.square(n)
    window.finish(start)
    return window.result(
        t_start, end_to_end={"squares_per_s": {"value": 1.0, "unit": "1/s"}},
        check={"segment_mismatch": 0.0, "unanswered": 0},
        attempted=cell.traffic["calls"], failed=0)
'''

TOY_KERNEL = '''TARGET = ("drivers.toy", "square")
MODULES = r"toy_module"


def shape(x):
    return (1000,)


def work(n):
    return n, 819 * n
'''


def test_new_files_are_found_by_name(tmp_path):
    """A later PR adds a configuration, a traffic mix, a driver, a kernel
    and metrics as files and entries, and edits nothing: the harness
    runs the new driver's ``serve``, the spy counts the new kernel's
    calls and its roofline is read by kind."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "benchmark"
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = json.load(open(b / "configs/metro-1hz.json"))
    conf["name"] = "metro-2hz"
    conf["probes"]["sample_period_s"] = [2]
    (b / "configs/metro-2hz.json").write_text(json.dumps(conf))
    (b / "configs/toy.json").write_text(json.dumps(
        {"check": {"limits": {"segment_mismatch": 0.01}}}))
    (b / "traffic/serve-closed-8.json").write_text(json.dumps(
        {**json.load(open(b / "traffic/serve-closed.json")), "clients": 8}))
    (b / "traffic/replay.json").write_text(json.dumps(
        {"driver": "toy", "calls": 3}))
    (b / "drivers/toy.py").write_text(TOY_DRIVER)
    (b / "kernels/toy.py").write_text(TOY_KERNEL)
    (b / "metrics/route_hits.closed.py").write_text(
        'def read(r):\n'
        '    return r.counter("route.cache.pair_hits") or None\n')
    (b / "metrics/toy_calls.replay.py").write_text(
        'def read(r):\n'
        '    return sum(n for k, n in r.calls.items() if k[0] == "toy")\n')
    (b / "metrics/toy_roofline.replay.py").write_text(
        'def read(r):\n    return r.roofline("toy")\n')
    spec["configs"] += [{"name": "metro-2hz", "source": "x", "why": "x",
                         "file": "benchmark/configs/metro-2hz.json",
                         "reduced": []},
                        {"name": "toy", "source": "x", "why": "x",
                         "file": "benchmark/configs/toy.json",
                         "reduced": []}]
    spec["workloads"] += [{"name": "metro-2hz.serve-closed-8",
                           "config": "metro-2hz", "chips": 1, "why": "x",
                           "traffic": "serve-closed-8"},
                          {"name": "toy.replay", "config": "toy",
                           "traffic": "replay", "chips": 1, "why": "x"}]
    spec["end_to_end"][0]["workloads"].append("metro-2hz.serve-closed-8")
    spec["end_to_end"].append({"name": "squares_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["toy.replay"]})
    spec["per_layer"] += [
        {"name": "route_hits.closed", "unit": "hits", "better": "higher",
         "source": "program_counter", "layer": "host prep",
         "moves": "traces_per_s", "workloads": ["metro-2hz.serve-closed-8"]},
        {"name": "toy_calls.replay", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "toy",
         "moves": "squares_per_s", "workloads": ["toy.replay"]},
        {"name": "toy_roofline.replay", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "toy",
         "moves": "squares_per_s", "workloads": ["toy.replay"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    code = (
        f"import sys; sys.path[:0] = ['benchmark', {ROOT!r}]\n"
        "import harness, run\n"
        "from readings import Readings\n"
        "c = harness.Cell('metro-2hz.serve-closed-8')\n"
        "r = Readings({'route.cache.pair_hits': 5}, {}, {}, None, 'x')\n"
        "print(c.config['probes']['sample_period_s'], c.traffic['clients'],"
        " c.driver.__name__, [m['name'] for m in c.end_to_end],"
        " {k: m.read(r) for k, m in c.readers().items()})\n"
        "run.native_build = lambda: 0.0\n"
        "rc = run.main(['--workload', 'toy.replay', '--seed', '5',"
        " '--seconds', '1', '--rehearse', '--trace', '1'])\n"
        "class Trace:\n"
        "    idle_share = None\n"
        "    def module_seconds(self, pattern):\n"
        "        return 1e-3 if pattern == 'toy_module' else 0.0\n"
        "r = Readings({}, {}, {('toy', 1000): 2}, Trace(), 'TPU v5 lite')\n"
        "print('rc', rc, 'toy roofline %.4f' % r.roofline('toy'))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0] == (
        "[2] 8 drivers.closed ['traces_per_s', 'setup_s'] "
        "{'route_hits.closed': 5}")
    window = next(ln for ln in lines if ln.startswith("bench window: "))
    assert json.loads(window[len("bench window: "):])["kernel_calls"] == 3
    line = next(ln for ln in lines if ln.startswith("bench rehearsal: "))
    line = json.loads(line[len("bench rehearsal: "):])["line"]
    assert line["correct"] is True
    assert line["metrics"]["toy_calls.replay"]["value"] == 3
    # two calls of 819,000 bytes at 819 GB/s in 1 ms of device time
    assert lines[-1] == "rc 3 toy roofline 0.2000"


def _run(*args, cwd=ROOT, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("REPORTER_TPU_PLATFORM", None)
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def _rehearsal(proc) -> dict:
    assert proc.returncode == 3, (proc.stdout[-3000:], proc.stderr[-3000:])
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("bench rehearsal: "))
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    return json.loads(line[len("bench rehearsal: "):])["line"]



@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_every_cell(cell):
    line = _rehearsal(_run("--workload", cell, "--seed", "3000000123",
                           "--seconds", "2", "--rehearse", "--trace", "1"))
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line["limits"])[-1] == "unanswered"


def test_no_chip_no_result():
    proc = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1")
    assert proc.returncode not in (0, 3)
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                cwd=tmp_path)
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == "" or "{" not in proc.stdout


@pytest.mark.parametrize("fault", ["answer", "half_batch"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = _rehearsal(_run("--workload", CELLS[0], "--seed", "77",
                           "--seconds", "2", "--rehearse", "--fault", fault))
    assert line["correct"] is False
    seg = line["limits"]["segment_mismatch"]
    assert seg["value"] > seg["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_check(cell):
    """The reference one step down in precision, in the program's place
    on a rehearsal's sample: the harness's own verdict reads not
    correct."""
    line = _rehearsal(_run("--workload", cell, "--seed", "78",
                           "--seconds", "2", "--rehearse", "--control", "1"))
    assert line["correct"] is False
    seg = line["limits"]["segment_mismatch"]
    assert seg["value"] > seg["limit"]
