"""The stream backlog cell's own tests, on the CPU (``test_benchmark.py``
rehearses it with every cell and fails its control):

- its files are found by name from its entry alone;
- the plain reference imports nothing of the program;
- the driver fails a run in which the worker lost a window, the row
  comparison holds a tile row to report times within ``TIME_TOL_S``, and
  the window comparison counts each missing segment, report and
  ``shape_used``;
- the spy counts the carried-state advance by (R, N, K, dtype), and its work
  is the one the kernel file states.

    python3 -m pytest benchmark/tests -q
"""
import ast
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never the chip

CELL = "metro-1hz.stream-backlog"


def test_the_cell_is_found_by_name():
    import harness
    c = harness.Cell(CELL)
    assert c.config["name"] == "metro-1hz-stream"
    assert c.driver.__name__ == "drivers.stream_backlog"
    assert [m["name"] for m in c.end_to_end] == ["traces_per_s", "setup_s"]
    names = sorted(c.readers())
    assert names == sorted(m["name"] for m in c.per_layer)
    assert len(names) == 8 and all(n.endswith(".stream") for n in names)
    r = harness.Cell(CELL, rehearse=True)
    assert r.traffic["vehicles"] < c.traffic["vehicles"]


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "ref", "stream.py")
    tree = ast.parse(open(path).read())
    mods = [n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    assert mods and not any(m.startswith("reporter_tpu") for m in mods)
    code = ("import sys; sys.path.insert(0, 'benchmark')\n"
            "import stream_gen, ref.stream\n"
            "assert not [m for m in sys.modules if "
            "m.startswith(('reporter_tpu', 'jax'))], 'imported'\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_lost_work_fails_the_run():
    import harness
    from drivers import stream_backlog
    stream_backlog.check_lost({"stream.windows": 9})
    for name in stream_backlog.LOST:
        with pytest.raises(harness.Failure, match=name):
            stream_backlog.check_lost({name: 1})


def test_row_comparison():
    from drivers.stream_backlog import row_mismatch
    tile = ("0_3599/1/42", "rtpu_stream.e00000003")
    text = "1,2,10,1,600,0,5,16,rtpu_stream,AUTO"
    want = (*tile, text, 5.2, 15.2)
    assert row_mismatch([(*tile, text)], [want]) == (0, 1)
    # a report 1 ms later writes the same row
    assert row_mismatch([(*tile, text)], [(*tile, text, 5.201, 15.201)]) \
        == (0, 1)
    # t1 on a whole second: within the tolerance the row may end there
    # or one second later
    on = (*tile, "1,2,10,1,600,0,5,15,rtpu_stream,AUTO", 5.0, 15.0)
    assert row_mismatch([(*tile, "1,2,10,1,600,0,5,16,rtpu_stream,AUTO")],
                        [on]) == (0, 1)
    # a second off, another epoch, a duplicate
    assert row_mismatch([(*tile, text)], [(*tile, text, 6.2, 15.2)]) \
        == (2, 1)
    assert row_mismatch([(tile[0], "rtpu_stream.e00000004", text)],
                        [want]) == (2, 1)
    assert row_mismatch([(*tile, text)] * 2, [want]) == (1, 1)
    assert row_mismatch([], [want, want]) == (2, 2)


def test_window_comparison():
    from drivers.stream_backlog import window_mismatch
    seg = {"segment_id": 7, "way_ids": [1], "length": 90,
           "queue_length": 0, "internal": False, "begin_shape_index": 0,
           "end_shape_index": 4, "start_time": 3.0, "end_time": 9.5}
    rep = {"id": 7, "t0": 3.0, "t1": 9.5, "length": 90, "queue_length": 0}
    want = {"segment_matcher": {"segments": [seg]},
            "datastore": {"reports": [rep]}, "shape_used": 4}
    assert window_mismatch([want], [want]) == (0, 3)
    late = {**want, "segment_matcher": {"segments": [
        {**seg, "end_time": 9.5015}]}}
    assert window_mismatch([late], [want]) == (0, 3)
    other = {**want, "segment_matcher": {"segments": [
        {**seg, "end_shape_index": 5}]}, "shape_used": 5}
    assert window_mismatch([other], [want]) == (2, 3)
    assert window_mismatch([None, want], [want, want]) == (3, 6)


def test_the_step_is_counted_by_its_shape():
    sys.path.insert(0, ROOT)
    os.environ.setdefault("REPORTER_TPU_PLATFORM", "cpu")
    import harness
    import roofline
    from reporter_tpu import ops
    from reporter_tpu.matcher.incremental import _round_inputs
    kernel = roofline.kernels()["incremental"]
    spy = harness.CallSpy()
    try:
        spy.on = True
        out = ops.incremental_step_batch(*_round_inputs(4, 2, 8),
                                         np.float32(4.07), np.float32(3.0))
        np.asarray(out[0])
    finally:
        spy.close()
    (key, n), = ((k, v) for k, v in spy.calls.items()
                 if k[0] == "incremental")
    assert key == ("incremental", 4, 2, 8, "float32") and n == 1
    ops_, nbytes = kernel.work(*key[1:])
    assert ops_ == 2 * 4 * 2 * 8 * 8
    assert nbytes == 4 * 2 * (8 * 4 + 8 + 64 * 4 + 9 + 8 * 4 + 4) \
        + 2 * 2 * 8 * 4
