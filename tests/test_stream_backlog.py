"""The streaming worker replaying a backlog under stream-time punctuation,
built by the in-process wiring the CLI and the benchmark share
(``streaming.worker.inprocess_worker``), on a seeded 40 x 40 grid:

- a backlog offered straight through and the same backlog offered with
  pauses write byte-identical tile files;
- the worker's tile rows and report windows are the plain reference's
  (``benchmark/ref/stream.py``, which decodes each window whole);
- the carried-state path answers most windows, each as ``report_many``
  answers the same window;
- ``--punctuate`` defaults to ``wall``, stream punctuation refuses an
  injected clock, and ``main()`` still runs in-process over ``--input``;
- after ``warm_incremental`` the stream compiles no carried-state step,
  and a second, identical stream compiles nothing.
"""
import inspect
import json
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402
import stream_gen  # noqa: E402
from reporter_tpu.matcher import SegmentMatcher  # noqa: E402
from reporter_tpu.matcher.params import MatchParams  # noqa: E402
from reporter_tpu.streaming import worker as stream_worker  # noqa: E402
from reporter_tpu.streaming.anonymiser import Anonymiser, \
    TileSink  # noqa: E402
from reporter_tpu.streaming.formatter import Formatter  # noqa: E402
from reporter_tpu.utils import metrics  # noqa: E402

SEED = 3000000123
#: every level reported and privacy 1, so each report reaches a file
LEVELS = "0,1,2"
PRIVACY = 1
VEHICLES = 24
TRIP_S = [240, 360]


@pytest.fixture(scope="module")
def city(tmp_path_factory):
    """(configuration, graph, graph path, the backlog's messages)."""
    conf = json.load(open(os.path.join(BENCH, "configs", "metro-1hz-stream.json")))
    conf["graph"].update(conf["rehearse"]["graph"])
    traffic = json.load(open(os.path.join(BENCH, "traffic",
                                          "stream-backlog.json")))
    traffic.update(traffic["rehearse"], vehicles=VEHICLES, trip_s=TRIP_S)
    net = gen.build_graph(conf, SEED)
    path = str(tmp_path_factory.mktemp("graph") / "city.npz")
    net.save(path)
    lines, _made = stream_gen.backlog(net, conf, traffic, SEED)
    return conf, net, path, lines


def _matcher(conf, net):
    return SegmentMatcher(net=net, params=MatchParams(**conf["matcher"]))


def _worker(conf, matcher, out_dir, **kw):
    s = conf["stream"]
    return stream_worker.inprocess_worker(
        matcher, Formatter.from_config(stream_gen.FORMATTER),
        Anonymiser(TileSink(out_dir), PRIVACY, s["quantisation_s"],
                   mode="auto", source="test"),
        reports=LEVELS, transitions=LEVELS,
        flush_interval_s=s["flush_interval_s"], report_flush_interval_s=1,
        punctuate="stream", **kw)


def _files(out_dir) -> dict:
    out = {}
    for root, dirs, files in os.walk(out_dir):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        for name in files:
            path = os.path.join(root, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, out_dir)] = f.read()
    return out


def test_a_paused_backlog_writes_the_same_tiles(city, tmp_path):
    conf, net, _path, lines = city
    straight = str(tmp_path / "straight")
    _worker(conf, _matcher(conf, net), straight).run(lines)

    def paced():
        for k, line in enumerate(lines):
            if k % (len(lines) // 4) == 0:
                time.sleep(1.1)   # past the 1 s report flush on a wall clock
            yield line

    paused = str(tmp_path / "paused")
    _worker(conf, _matcher(conf, net), paused).run(paced())
    a, b = _files(straight), _files(paused)
    assert len(a) >= 2
    assert a == b


def test_the_worker_agrees_with_the_plain_reference(city, tmp_path):
    from drivers.stream_backlog import read_tiles, row_mismatch
    from ref.pipeline import Reference
    from ref.stream import StreamReference
    conf, net, _path, lines = city
    out = str(tmp_path / "tiles")
    before = metrics.default.counter("stream.windows")
    _worker(conf, _matcher(conf, net), out).run(lines)
    windows = metrics.default.counter("stream.windows") - before

    ref = Reference(net, conf["matcher"], conf["service"]["threshold_sec"])
    levels = [int(x) for x in LEVELS.split(",")]
    s = conf["stream"]
    sr = StreamReference(lambda reqs: [ref.report(r) for r in reqs],
                         PRIVACY, s["quantisation_s"], s["flush_interval_s"],
                         1, "auto", "test", levels, levels)
    observed, rows = sr.run(lines)
    have = read_tiles(out)
    assert len(rows) == len(observed) > 20   # privacy 1 keeps every row
    assert row_mismatch(have, rows) == (0, len(rows))
    assert windows == sr.windows > 100


def test_the_carried_state_path_answers_as_report_many(city, tmp_path):
    conf, net, _path, lines = city
    worker = _worker(conf, _matcher(conf, net), str(tmp_path / "tiles"))
    submit = worker.batcher.submit_many
    service = submit.__self__
    served = []

    def both(tb):
        out = submit(tb)
        served.append((out, service.report_many(tb)))
        return out

    worker.batcher.submit_many = both
    c0 = {k: metrics.default.counter(k)
          for k in ("stream.windows", "match.incremental.matches")}
    worker.run(lines)
    c = {k: metrics.default.counter(k) - v for k, v in c0.items()}
    assert c["match.incremental.matches"] > c["stream.windows"] / 2
    n = 0
    for out, again in served:
        for a, b in zip(out, again):
            assert a["datastore"] == b["datastore"]
            assert a.get("shape_used") == b.get("shape_used")
            assert a["segment_matcher"]["segments"] \
                == b["segment_matcher"]["segments"]
            n += 1
    assert n == c["stream.windows"]


def test_punctuate_defaults_to_wall_and_main_runs_inprocess(
        city, tmp_path, monkeypatch):
    sig = inspect.signature(stream_worker.StreamWorker)
    assert sig.parameters["punctuate"].default == "wall"
    _conf, _net, path, lines = city
    # an injected clock paces wall punctuation only
    with pytest.raises(ValueError, match="injected clock"):
        stream_worker.StreamWorker(
            Formatter.from_config(stream_gen.FORMATTER), lambda b: None,
            Anonymiser(TileSink(str(tmp_path / "x")), 1, 3600),
            clock=lambda: 0.0, punctuate="stream")
    src = tmp_path / "backlog.sv"
    src.write_text("\n".join(lines) + "\n")
    seen = {}
    real = stream_worker.inprocess_worker

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(stream_worker, "inprocess_worker", spy)
    out = tmp_path / "tiles"
    rc = stream_worker.main([
        "-f", stream_gen.FORMATTER, "--graph", path, "-p", "1",
        "-q", "3600", "-i", "15", "-s", "test", "-o", str(out),
        "-r", LEVELS, "-x", LEVELS, "--input", str(src)])
    assert rc == 0
    assert seen["punctuate"] == "wall"
    assert _files(str(out))


def test_no_compile_after_the_warm_up(city, tmp_path):
    import jax.monitoring
    conf, net, _path, lines = city
    compiled = []
    on = threading.Event()

    def listen(name, _dur, fun_name="?", **_kw):
        if on.is_set() and name.endswith("backend_compile_duration"):
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    matcher = _matcher(conf, net)
    assert matcher.warm_incremental(VEHICLES) == 12
    on.set()
    try:
        _worker(conf, matcher, str(tmp_path / "first")).run(lines)
        first = list(compiled)
        _worker(conf, matcher, str(tmp_path / "second")).run(lines)
    finally:
        on.clear()
    assert not [f for f in first if "incremental" in f], first
    assert compiled == first
