"""Batch pipeline tests: gather -> batched match -> tile report, end to end
over local files (the reference's S3 path is gated off in this image)."""
import gzip
import hashlib
import os

import numpy as np
import pytest

from reporter_tpu.core.types import Segment
from reporter_tpu.matcher import SegmentMatcher
from reporter_tpu.pipeline.simple_reporter import (
    _windows_of, gather_traces, match_traces, report_tiles)
from reporter_tpu.synth import build_grid_city, generate_trace


@pytest.fixture(scope="module")
def city():
    return build_grid_city(rows=10, cols=10, spacing_m=200.0, seed=4,
                           service_road_fraction=0.0, internal_fraction=0.0)


def make_part_file(city, path, n_traces=5, seed=0):
    """Pipe-separated part file shaped like the reference's default valuer
    expects: col1=uuid, col0=time, col9=lat, col10=lon, col5=accuracy."""
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n_traces):
        tr = None
        while tr is None:
            tr = generate_trace(city, f"veh-{seed}-{i}", rng, noise_m=3.0,
                                min_route_edges=8)
        for p in tr.points:
            cols = ["x"] * 11
            cols[0] = str(p["time"])
            cols[1] = tr.uuid
            cols[5] = str(p["accuracy"])
            cols[9] = str(p["lat"])
            cols[10] = str(p["lon"])
            lines.append("|".join(cols))
    with gzip.open(path, "wt") as f:
        f.write("\n".join(lines))
    return lines


class TestWindows:
    def test_split_at_inactivity(self):
        pts = [{"time": t} for t in (0, 10, 20, 300, 310, 320)]
        wins = list(_windows_of(pts, inactivity=120))
        assert [len(w) for w in wins] == [3, 3]

    def test_short_windows_dropped(self):
        pts = [{"time": t} for t in (0, 300, 310)]
        wins = list(_windows_of(pts, inactivity=120))
        assert [len(w) for w in wins] == [2]


class TestPipelineEndToEnd:
    def test_three_stages(self, city, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        src_dir = tmp_path / "src"
        src_dir.mkdir()
        make_part_file(city, str(src_dir / "part-000.gz"), n_traces=4, seed=1)
        make_part_file(city, str(src_dir / "part-001.gz"), n_traces=4, seed=2)

        trace_dir = gather_traces(str(src_dir), ".*",
                                  "lambda l: (lambda c: [c[1], c[0], c[9], "
                                  "c[10], c[5]])(l.split('|'))",
                                  "%Y-%m-%d %H:%M:%S",
                                  [-90.0, -180.0, 90.0, 180.0], concurrency=2)
        shard_files = [os.path.join(r, f)
                       for r, _d, fs in os.walk(trace_dir) for f in fs]
        assert shard_files, "stage 1 produced no shards"

        matcher = SegmentMatcher(net=city)
        match_dir = match_traces(
            trace_dir, matcher, "auto", {0, 1, 2}, {0, 1, 2},
            quantisation=3600, inactivity=120, source="test")
        tile_files = [os.path.join(r, f)
                      for r, _d, fs in os.walk(match_dir) for f in fs]
        assert tile_files, "stage 2 produced no tile rows"
        # rows have the 10-column layout with uppercased mode
        with open(tile_files[0]) as f:
            cols = f.readline().strip().split(",")
        assert len(cols) == 10 and cols[9] == "AUTO" and cols[3] == "1"

        dest = tmp_path / "dest"
        report_tiles(match_dir, str(dest), privacy=1, concurrency=2)
        out_files = [os.path.join(r, f)
                     for r, _d, fs in os.walk(dest) for f in fs]
        assert out_files, "stage 3 wrote nothing"
        with open(out_files[0]) as f:
            assert f.readline().strip() == Segment.column_layout()

    def test_privacy_cull_removes_rare_pairs(self, city, tmp_path):
        match_dir = tmp_path / "matches" / "0_3599" / "0"
        match_dir.mkdir(parents=True)
        rows = (["5,6,10,1,600,0,0,10,src,AUTO\n"] * 3
                + ["7,8,10,1,600,0,0,10,src,AUTO\n"])
        with open(match_dir / "42", "w") as f:
            f.writelines(rows)
        dest = tmp_path / "out"
        report_tiles(str(tmp_path / "matches"), str(dest), privacy=2,
                     concurrency=1)
        out_files = [os.path.join(r, f)
                     for r, _d, fs in os.walk(dest) for f in fs]
        (path,) = out_files
        with open(path) as f:
            body = f.read()
        assert body.count("5,6,") == 3
        assert "7,8," not in body


    def test_report_workers_never_touch_a_backend(self, tmp_path):
        """One process per chip: stage 3's worker processes run after
        the parent matched on the chip, so they must never initialise a
        JAX backend. A bogus platform makes any backend init raise in
        a worker, and its tile would then be missing."""
        import subprocess
        import sys
        match_dir = tmp_path / "matches" / "0_3599" / "0"
        match_dir.mkdir(parents=True)
        for tile in ("42", "43"):
            with open(match_dir / tile, "w") as f:
                f.write("5,6,10,1,600,0,0,10,src,AUTO\n")
        dest = tmp_path / "out"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = ("from reporter_tpu.pipeline.simple_reporter import "
                "report_tiles\n"
                f"report_tiles({str(tmp_path / 'matches')!r}, "
                f"{str(dest)!r}, privacy=1, concurrency=2)\n"
                "from jax._src import xla_bridge\n"
                "assert not xla_bridge._backends\n")
        env = dict(os.environ, PYTHONPATH=repo,
                   JAX_PLATFORMS="no_such_platform")
        env.pop("REPORTER_TPU_PLATFORM", None)
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        out_files = [f for _r, _d, fs in os.walk(dest) for f in fs]
        assert len(out_files) == 2, (out_files, proc.stderr[-800:])


class TestLongWindowChunking:
    """Windows beyond the largest padding bucket are chunked with a
    holdback overlap rather than truncated."""

    def _points(self, n, dt=1):
        return [{"time": 1500000000 + i * dt, "lat": 14.0 + i * 1e-4,
                 "lon": 121.0} for i in range(n)]

    def test_short_window_untouched(self):
        from reporter_tpu.pipeline.simple_reporter import _windows_of
        pts = self._points(500)
        ws = list(_windows_of(pts, inactivity=120))
        assert len(ws) == 1 and len(ws[0]) == 500

    def test_long_window_chunks_cover_all_points(self):
        from reporter_tpu.pipeline.simple_reporter import (
            MAX_WINDOW_POINTS, _windows_of)
        pts = self._points(2500)
        ws = list(_windows_of(pts, inactivity=120))
        assert all(len(w) <= MAX_WINDOW_POINTS for w in ws)
        covered = {p["time"] for w in ws for p in w}
        assert covered == {p["time"] for p in pts}

    def test_chunk_overlap_spans_holdback(self):
        from reporter_tpu.pipeline.simple_reporter import _windows_of
        pts = self._points(2500)
        ws = list(_windows_of(pts, inactivity=120, holdback_s=15))
        for a, b in zip(ws[:-1], ws[1:]):
            overlap_start = b[0]["time"]
            assert a[-1]["time"] - overlap_start > 15  # covers holdback
            assert overlap_start > a[0]["time"]        # but makes progress

    def test_inactivity_split_still_applies(self):
        from reporter_tpu.pipeline.simple_reporter import _windows_of
        pts = self._points(100)
        pts[50]["time"] += 1000  # gap
        for p in pts[51:]:
            p["time"] += 1000
        ws = list(_windows_of(pts, inactivity=120))
        assert len(ws) == 2


VALUER = ("lambda l: (lambda c: [c[1], c[0], c[9], c[10], c[5]])"
          "(l.split('|'))")


def _day_lines(city, uuid, rng, legs, t0):
    """One vehicle's day as pipe-separated lines: ``legs`` lists the
    trips, each a number of chained routes driven back to back, with
    a stop longer than the inactivity between trips."""
    lines = []
    t = t0
    for n_routes in legs:
        for _ in range(n_routes):
            tr = None
            while tr is None:
                tr = generate_trace(city, uuid, rng, noise_m=3.0,
                                    min_route_edges=8, start_time=t)
            for p in tr.points:
                cols = ["x"] * 11
                cols[0] = str(p["time"])
                cols[1] = uuid
                cols[5] = str(p["accuracy"])
                cols[9] = str(p["lat"])
                cols[10] = str(p["lon"])
                lines.append("|".join(cols))
            t = tr.points[-1]["time"] + 1
        t += 600
    return lines


@pytest.fixture(scope="module")
def day_archive(city, tmp_path_factory):
    """Five vehicles' days in two part files; vehicle 0 drives one trip
    of twenty routes, longer than two of the longest window, so its trip
    is cut into three or more chunks with a holdback overlap."""
    src = tmp_path_factory.mktemp("archive")
    rng = np.random.default_rng(11)
    days = [_day_lines(city, f"veh-{i}", rng, [20] if i == 0 else [2, 1],
                       1_500_000_000 + 7 * i) for i in range(5)]
    for k in range(2):
        with gzip.open(src / f"part-{k}.gz", "wt") as f:
            f.write("\n".join(ln for d in days[k::2] for ln in d))
    return str(src)


def _pipeline_deltas(fn):
    """Run ``fn()``; return its result and the pipeline's counter and
    timer-count deltas over it."""
    from reporter_tpu.utils import metrics
    before = metrics.default.snapshot()
    out = fn()
    after = metrics.default.snapshot()
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()
                if k.startswith("pipeline.")}
    timers = {k: v["count"] - before["timers"].get(k, {"count": 0})["count"]
              for k, v in after["timers"].items() if k.startswith("pipeline.")}
    return out, counters, timers


def _run_stages(archive, city, device_batch, dest, matcher=None):
    """Stages 1-3 over ``archive`` -> (stage-2 dir, counter deltas,
    timer count deltas)."""
    def stages():
        trace_dir = gather_traces(archive, ".*", VALUER, "%Y-%m-%d %H:%M:%S",
                                  [-90.0, -180.0, 90.0, 180.0], concurrency=2)
        match_dir = match_traces(
            trace_dir, matcher or SegmentMatcher(net=city), "auto",
            {0, 1, 2}, {0, 1, 2}, quantisation=3600, inactivity=120,
            source="test", device_batch=device_batch)
        report_tiles(match_dir, str(dest), privacy=1, concurrency=2)
        return match_dir
    return _pipeline_deltas(stages)


def _sorted_files(root):
    """{path relative to its tile directory: sorted lines} of every file
    under ``root`` (stage 3 names a file by a hash of its stage-2 path,
    so the directory is the key there)."""
    out = {}
    for r, _d, fs in os.walk(root):
        for f in fs:
            with open(os.path.join(r, f)) as fh:
                out[os.path.relpath(r, root)] = sorted(fh.readlines())
    return out


class TestBackfillBatching:
    """Stage 2 pools windows across shards into device batches of
    ``device_batch``; the results must not depend on how the windows
    fall into batches."""

    def test_device_batch_1_and_512_give_identical_tiles(
            self, city, day_archive, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        one, c1, _t = _run_stages(day_archive, city, 1, tmp_path / "d1")
        many, c512, _t = _run_stages(day_archive, city, 512,
                                     tmp_path / "d512")
        assert c1["pipeline.batches"] == c1["pipeline.windows"]
        assert c512["pipeline.batches"] == 1
        assert _sorted_files(one) == _sorted_files(many)
        tiles = _sorted_files(tmp_path / "d512")
        assert tiles and tiles == _sorted_files(tmp_path / "d1")

    def test_windows_straddling_device_calls_report_each_pair_once(
            self, city, day_archive, tmp_path, monkeypatch):
        """Vehicle 0's chunks go to the device in different calls
        (device_batch 2); the seam floor, one per uuid for the whole
        stage, still drops the re-presented pairs, so no (segment, next,
        times) row appears twice in a file and the rows equal those of
        one call for every window."""
        monkeypatch.chdir(tmp_path)
        split, c2, _t = _run_stages(day_archive, city, 2, tmp_path / "d2")
        whole, _c, _t = _run_stages(day_archive, city, 512, tmp_path / "dw")
        # nine trips; vehicle 0's one trip cut into at least three chunks,
        # so at least one of its seams falls between two calls
        assert c2["pipeline.windows"] >= 9 + 2
        rows = _sorted_files(split)
        assert rows == _sorted_files(whole)
        for lines in rows.values():
            assert len(lines) == len(set(lines))

    def test_counters_move_as_stated(self, city, day_archive, tmp_path,
                                     monkeypatch):
        from reporter_tpu.pipeline.simple_reporter import _window_spans
        monkeypatch.chdir(tmp_path)
        lines = []
        for part in sorted(os.listdir(day_archive)):
            with gzip.open(os.path.join(day_archive, part), "rt") as f:
                lines += [ln.split("|") for ln in f.read().splitlines()]
        by_uuid = {}
        for c in lines:
            by_uuid.setdefault(c[1], []).append(int(c[0]))
        spans = [(lo, hi) for ts in by_uuid.values()
                 for lo, hi in _window_spans(sorted(ts), 120)]
        match_dir, c, t = _run_stages(day_archive, city, 3,
                                      tmp_path / "d")
        assert c["pipeline.windows"] == len(spans)
        assert c["pipeline.points"] == sum(hi - lo for lo, hi in spans)
        assert c["pipeline.batches"] == -(-len(spans) // 3)
        assert c["pipeline.rows"] == sum(
            len(v) for v in _sorted_files(match_dir).values())
        assert not c.get("pipeline.batch_failures")
        assert not c.get("pipeline.report_failures")
        assert t["pipeline.gather"] == t["pipeline.match"] == 1
        assert t["pipeline.report"] == 1
        assert t["pipeline.parse"] >= 1
        assert t["pipeline.match_many"] == t["pipeline.rows"] \
            == c["pipeline.batches"]

    def test_a_failed_batch_or_report_is_counted(self, city, day_archive,
                                                 tmp_path, monkeypatch):
        """A planted ``match_many`` failure loses its batch, and a
        ``report()`` failure its window: each is counted, never silent."""
        monkeypatch.chdir(tmp_path)

        class Broken:
            def match_many(self, tb):
                raise RuntimeError("planted")

        match_dir, c, _t = _run_stages(day_archive, city, 4,
                                       tmp_path / "d1", matcher=Broken())
        assert c["pipeline.batch_failures"] == c["pipeline.batches"] > 0
        assert not _sorted_files(match_dir)
        import importlib
        report_mod = importlib.import_module("reporter_tpu.service.report")

        def boom(*_a, **_kw):
            raise ValueError("planted")

        monkeypatch.setattr(report_mod, "report", boom)
        _m, c, _t = _run_stages(day_archive, city, 512, tmp_path / "d2")
        assert c["pipeline.report_failures"] == c["pipeline.windows"] > 0
        assert not c.get("pipeline.batch_failures")


def test_warm_builds_every_shape_match_many_forms(city, monkeypatch):
    """``SegmentMatcher.warm`` runs each power-of-two (rows, T) up to a
    chunk and the bucket of the longest trace; match_many over traces up
    to that length then compiles nothing."""
    import jax.monitoring

    from reporter_tpu import native
    if not native.available():
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(5)
    traces = []
    while len(traces) < 12:
        tr = generate_trace(city, f"w{len(traces)}", rng, noise_m=3.0,
                            min_route_edges=2)
        if tr is not None and len(tr.points) >= 20:
            req = tr.request_json()
            req["trace"] = req["trace"][:64]
            traces.append(req)
    monkeypatch.setenv("REPORTER_TPU_DECODE_CHUNK", "8")
    m = SegmentMatcher(net=city)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    from reporter_tpu.ops import batch_pad_multiple
    pad = batch_pad_multiple() or 1
    rows = (-(-8 // pad) * pad).bit_length()  # powers of two 1 .. chunk
    assert m.warm(traces[:4], 64) == 3 * rows  # T 16, 32, 64
    n = len(compiles)
    for k in (1, 3, 7, 12):
        assert all(r is not None for r in m.match_many(traces[:k]))
    assert len(compiles) == n


def _parse_part_file_per_line(path, valuer, time_pattern, bbox, dest_dir):
    """The gather's parse as it was before its memos and its blocks: a
    shard hash, a host verdict and a calendar epoch for every line, and
    each probe a CSV text row. The oracle for ``_parse_part_file``'s
    shard files."""
    import calendar
    import hashlib
    import math
    import time
    from reporter_tpu.parallel import host_uuid_filter
    uuid_filter = host_uuid_filter()
    fast_time = time_pattern == "%Y-%m-%d %H:%M:%S"
    opener = gzip.open if path.endswith(".gz") else open
    shards = {}
    with opener(path, "rt") as f:
        for line in f:
            try:
                uuid, tm, lat, lon, acc = valuer(line)
                if uuid_filter is not None and not uuid_filter(str(uuid)):
                    continue
                lat = float(lat)
                lon = float(lon)
                if lat < bbox[0] or lat > bbox[2] or \
                        lon < bbox[1] or lon > bbox[3]:
                    continue
                if isinstance(tm, str) and not tm.isdigit():
                    if fast_time:
                        st = time.struct_time((
                            int(tm[0:4]), int(tm[5:7]), int(tm[8:10]),
                            int(tm[11:13]), int(tm[14:16]), int(tm[17:19]),
                            0, 0, 0))
                    else:
                        st = time.strptime(tm, time_pattern)
                    epoch = calendar.timegm(st)
                else:
                    epoch = int(tm)
                acc = min(int(math.ceil(float(acc))), 1000)
            except Exception:
                continue
            shard = hashlib.sha1(str(uuid).encode()).hexdigest()[:3]
            shards.setdefault(shard, []).append(
                f"{uuid},{epoch},{lat},{lon},{acc}\n")
    pid = os.getpid()
    for shard, rows in shards.items():
        with open(os.path.join(dest_dir, f"{shard}.{pid}"), "a") as f:
            f.write("".join(rows))


def _gather_cases_part(path):
    """A part of 40 vehicles whose lines cross an hour and a day boundary
    in both time formats, with malformed lines, probes outside the bbox,
    numeric epochs, and fractional and over-1,000 m accuracies."""
    import datetime
    rng = np.random.default_rng(7)
    t0 = datetime.datetime(2023, 12, 31, 22, 58, 30)
    lines = ["", "garbage", "a|b|c", "|".join(["x"] * 11),
             "2023-12-31 23:00:00|veh-x|x|x|x|abc|x|x|x|40.5|-74.0",
             "2023-13-31 23:00:00|veh-x|x|x|x|5|x|x|x|40.5|-74.0",
             "2023-12-31 23:0|veh-x|x|x|x|5|x|x|x|40.5|-74.0"]
    for i in range(4000):
        t = t0 + datetime.timedelta(seconds=int(i * 1.9))
        cols = ["x"] * 11
        cols[1] = f"veh-{i % 40}"
        kind = (i // 40) % 5  # each vehicle in every time format
        cols[0] = (str(int(t.replace(tzinfo=datetime.timezone.utc)
                           .timestamp())) if kind == 1
                   else t.strftime("%d/%m/%Y %H:%M:%S") if kind == 2
                   else t.strftime("%Y-%m-%d %H:%M:%S"))
        cols[5] = str(rng.choice(["3", "4.2", "999.01", "1000", "1500.5"]))
        cols[9] = str(40.0 + rng.uniform(-0.2, 1.2))  # some beyond 41.0
        cols[10] = str(-74.5 + rng.uniform(0.0, 1.0))
        lines.append("|".join(cols))
    with gzip.open(path, "wt") as f:
        f.write("\n".join(lines))
    return lines


class TestGather:
    """Stage 1: a uuid's shard and an hour's epoch are computed once per
    part; the shard files decode to the per-line parse's records, file
    for file and in order."""

    @pytest.mark.parametrize("hosts", ["one", "two"])
    @pytest.mark.parametrize("time_pattern",
                             ["%Y-%m-%d %H:%M:%S", "%d/%m/%Y %H:%M:%S"])
    def test_shard_files_match_the_per_line_parse(self, tmp_path,
                                                  monkeypatch, hosts,
                                                  time_pattern):
        import functools
        from reporter_tpu.parallel import host_uuid_filter
        from reporter_tpu.pipeline.simple_reporter import (
            DEFAULT_VALUER, _parse_part_file)
        if hosts == "two":
            monkeypatch.setenv("REPORTER_TPU_NUM_PROCESSES", "2")
            monkeypatch.setenv("REPORTER_TPU_PROCESS_ID", "1")
        else:
            monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
            monkeypatch.delenv("REPORTER_TPU_PROCESS_ID", raising=False)
        part = str(tmp_path / "part-0.gz")
        lines = _gather_cases_part(part)
        valuer = eval(DEFAULT_VALUER, {"functools": functools})
        bbox = [39.9, -74.5, 41.0, -73.5]
        old, new = tmp_path / "old", tmp_path / "new"
        old.mkdir()
        new.mkdir()
        _parse_part_file_per_line(part, valuer, time_pattern, bbox, str(old))
        n_lines, probes, hashes, hours = _parse_part_file(
            part, valuer, time_pattern, bbox, str(new), host_uuid_filter())
        want = {f: _text_records(old / f) for f in os.listdir(old)}
        got = {f: _block_records(new / f) for f in os.listdir(new)}
        assert want and got == want
        assert n_lines == len(lines)
        assert probes == sum(len(v) for v in want.values())
        kept = {r[0] for v in want.values() for r in v}
        assert len(kept) == 40 if hosts == "one" else 0 < len(kept) < 40
        assert hashes == 42  # the 40 vehicles, veh-x and the all-x line
        # 22:58:30 on 31 December to 01:05:08 on 1 January
        assert hours == (4 if time_pattern.startswith("%Y") else 0)

    def test_a_spawned_worker_imports_no_jax(self, tmp_path):
        """A gather worker reaches the multi-host uuid filter without
        importing JAX, which each spawned worker would otherwise pay for
        at start-up; the package's JAX-backed names still resolve."""
        import subprocess
        import sys
        from reporter_tpu.parallel import host_uuid_filter, make_mesh
        assert callable(make_mesh) and callable(host_uuid_filter)
        part = str(tmp_path / "part-0.gz")
        _gather_cases_part(part)
        dest = tmp_path / "traces"
        dest.mkdir()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import multiprocessing, sys\n"
            "from reporter_tpu.pipeline import simple_reporter as sr\n"
            "recv, send = multiprocessing.Pipe(duplex=False)\n"
            f"sr._gather_worker([{part!r}], sr.DEFAULT_VALUER, "
            f"'%Y-%m-%d %H:%M:%S', [-90.0, -180.0, 90.0, 180.0], "
            f"{str(dest)!r}, (2, 1), send)\n"
            "tally = recv.recv()\n"
            "assert tally is not None and tally[1] > 0, tally\n"
            "assert 'jax' not in sys.modules\n"
            "print('ok')\n")
        env = dict(os.environ, PYTHONPATH=repo,
                   REPORTER_TPU_NUM_PROCESSES="2",
                   REPORTER_TPU_PROCESS_ID="1")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert proc.stdout.strip() == "ok"
        assert os.listdir(dest)

    def test_counters_and_a_lost_part(self, day_archive, tmp_path,
                                      monkeypatch):
        """On the two-part day archive every line is kept and each uuid
        hashed at most once a part; a third part that is not gzip counts
        one failure, and the other two are still gathered."""
        import shutil
        from reporter_tpu.utils import metrics
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        uuids, n_lines = set(), 0
        parts = sorted(os.listdir(day_archive))
        for part in parts:
            with gzip.open(os.path.join(day_archive, part), "rt") as f:
                body = f.read().splitlines()
            n_lines += len(body)
            uuids |= {ln.split("|")[1] for ln in body}

        def gather(src):
            before = metrics.default.snapshot()["counters"]
            out = gather_traces(src, ".*", VALUER, "%Y-%m-%d %H:%M:%S",
                                [-90.0, -180.0, 90.0, 180.0], concurrency=2)
            after = metrics.default.snapshot()["counters"]
            rows = sorted(rec for r, _d, fs in os.walk(out) for f in fs
                          for rec in _block_records(os.path.join(r, f)))
            return {k: v - before.get(k, 0) for k, v in after.items()
                    if k.startswith("pipeline.gather")}, rows

        c, rows = gather(day_archive)
        assert c["pipeline.gather.probes"] == n_lines == len(rows)
        assert c["pipeline.gather.lines"] >= c["pipeline.gather.probes"]
        assert 0 < c["pipeline.gather.uuid_hashes"] \
            <= len(uuids) * len(parts)
        assert c["pipeline.gather.hour_epochs"] == 0  # numeric epochs
        assert c["pipeline.gather_failures"] == 0

        src = tmp_path / "with-bad-part"
        shutil.copytree(day_archive, src)
        (src / "part-2.gz").write_bytes(b"not a gzip stream\n" * 8)
        c3, rows3 = gather(str(src))
        assert c3["pipeline.gather_failures"] == 1
        assert c3["pipeline.gather.probes"] == n_lines
        assert rows3 == rows


def _tile_dir(root, tiles):
    """A stage-2 directory under ``root`` of one hour's tile files, one
    for each name of ``tiles``, each a row of its own pair."""
    hour = root / "0_3599" / "0"
    hour.mkdir(parents=True)
    for k, tile in enumerate(tiles):
        with open(hour / tile, "w") as f:
            f.write(f"{k},{k + 1},10,1,600,0,0,10,src,AUTO\n")
    return str(root)


def _fork_server_pid():
    """The pid of this process's fork server, None before it starts."""
    from multiprocessing import forkserver
    return forkserver._forkserver._forkserver_pid


class TestWorkerLaunch:
    """Stages 1 and 3 start their workers through one launcher, from a
    fork server that has imported the pipeline's module where the
    platform offers one: the start method changes no output, the
    workers see the caller's host share, nothing imports JAX, and a
    worker that dies is counted."""

    def test_spawned_and_preloaded_workers_write_the_same_files(
            self, city, day_archive, tmp_path, monkeypatch):
        import multiprocessing
        from reporter_tpu.pipeline import simple_reporter as sr
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        monkeypatch.delenv("REPORTER_TPU_PROCESS_ID", raising=False)
        contexts = {"spawn": multiprocessing.get_context("spawn"),
                    "server": sr._MP}

        def shards(trace_dir):
            # a shard file is named by its worker's pid: compare each
            # shard's files by their bytes
            out = {}
            for f in os.listdir(trace_dir):
                with open(os.path.join(trace_dir, f), "rb") as fh:
                    out.setdefault(f.split(".")[0], []).append(fh.read())
            return {k: sorted(v) for k, v in out.items()}

        def tiles(root):
            out = {}
            for r, _d, fs in os.walk(root):
                for f in fs:
                    with open(os.path.join(r, f), "rb") as fh:
                        out[os.path.relpath(os.path.join(r, f), root)] = \
                            fh.read()
            return out

        traces = {}
        for name, ctx in contexts.items():
            monkeypatch.setattr(sr, "_MP", ctx)
            traces[name] = gather_traces(
                day_archive, ".*", VALUER, "%Y-%m-%d %H:%M:%S",
                [-90.0, -180.0, 90.0, 180.0], concurrency=2)
        assert shards(traces["spawn"]) == shards(traces["server"]) != {}
        match_dir = match_traces(
            traces["server"], SegmentMatcher(net=city), "auto", {0, 1, 2},
            {0, 1, 2}, quantisation=3600, inactivity=120, source="test")
        for name, ctx in contexts.items():
            monkeypatch.setattr(sr, "_MP", ctx)
            report_tiles(match_dir, str(tmp_path / name), privacy=1,
                         concurrency=2)
        assert tiles(tmp_path / "spawn") == tiles(tmp_path / "server") != {}

    def test_a_worker_keeps_the_callers_host_share(self, day_archive,
                                                   tmp_path, monkeypatch):
        """The host share set after the fork server started reaches the
        next gather's workers: the caller reads it, not the worker,
        whose environment is the server's."""
        from reporter_tpu.parallel import host_uuid_filter
        from reporter_tpu.pipeline import simple_reporter as sr
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        monkeypatch.delenv("REPORTER_TPU_PROCESS_ID", raising=False)

        def uuids():
            out = gather_traces(day_archive, ".*", VALUER,
                                "%Y-%m-%d %H:%M:%S",
                                [-90.0, -180.0, 90.0, 180.0], concurrency=2)
            return {rec[0] for f in os.listdir(out)
                    for rec in _block_records(os.path.join(out, f))}

        every = uuids()
        server = _fork_server_pid()
        monkeypatch.setenv("REPORTER_TPU_NUM_PROCESSES", "2")
        monkeypatch.setenv("REPORTER_TPU_PROCESS_ID", "1")
        kept = uuids()
        owned = host_uuid_filter(2, 1)
        assert kept == {u for u in every if owned(u)}
        assert 0 < len(kept) < len(every)
        if sr._MP.get_start_method() == "forkserver":
            assert server is not None and _fork_server_pid() == server

    def test_a_jax_callers_workers_import_no_jax_and_see_its_environment(
            self, tmp_path):
        """A caller that has imported JAX and run it starts workers from
        a server that has not: a worker inherits the server's modules at
        its fork, so a worker with no ``jax`` module shows a server with
        none. A variable the caller sets once the server is up reaches
        the next workers."""
        import subprocess
        import sys
        (tmp_path / "probe.py").write_text(
            "import os\n"
            "import sys\n\n\n"
            "def look(chunk, results):\n"
            "    for _ in chunk:\n"
            "        results.send((sorted(m for m in sys.modules\n"
            "                             if m.split('.')[0] == 'jax'),\n"
            "                      os.environ.get('PROBE_SET_LATE')))\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import os\n"
            "import jax.numpy as jnp\n"
            "jnp.zeros(4).block_until_ready()\n"
            "import probe\n"
            "from reporter_tpu.pipeline import simple_reporter as sr\n"
            "from reporter_tpu.utils import metrics\n"
            "print(sr._run_workers(probe.look, [[0], [1, 2]]))\n"
            "os.environ['PROBE_SET_LATE'] = 'yes'\n"
            "print(sr._run_workers(probe.look, [[0]]))\n"
            "print(sr._MP.get_start_method(),\n"
            "      metrics.default.counter('pipeline.worker_starts'),\n"
            "      metrics.default.counter("
            "'pipeline.worker_starts.preloaded'))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join([repo, str(tmp_path)]))
        env.pop("PROBE_SET_LATE", None)
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        first, second, starts = proc.stdout.splitlines()
        assert first == "[([], None), ([], None), ([], None)]"
        assert second == "[([], 'yes')]"
        method, n, preloaded = starts.split()
        assert n == "3"
        assert preloaded == ("3" if method == "forkserver" else "0")

    def test_a_dead_tile_worker_is_counted(self, tmp_path, monkeypatch):
        """The first of three stage-3 workers exits before it reports:
        its tile counts one failure, and the other two are written."""
        from reporter_tpu.pipeline import simple_reporter as sr

        class FirstWorkerDies:
            """The launcher's context, but its first worker exits at once."""

            def __init__(self, ctx):
                self.ctx = ctx
                self.started = 0

            def Pipe(self, duplex):
                return self.ctx.Pipe(duplex=duplex)

            def Process(self, target, args):
                self.started += 1
                if self.started == 1:
                    return self.ctx.Process(target=os._exit, args=(9,))
                return self.ctx.Process(target=target, args=args)

        match_dir = _tile_dir(tmp_path / "matches", ["41", "42", "43"])
        dest = tmp_path / "out"
        monkeypatch.setattr(sr, "_MP", FirstWorkerDies(sr._MP))
        _out, c, t = _pipeline_deltas(lambda: report_tiles(
            match_dir, str(dest), privacy=1, concurrency=3))
        assert c["pipeline.tile_failures"] == 1
        assert c["pipeline.report.tiles"] == c["pipeline.report.rows"] == 2
        assert c["pipeline.worker_starts"] == 3
        written = sorted(os.path.relpath(r, dest)
                         for r, _d, fs in os.walk(dest) if fs)
        assert written == [os.path.join("0_3599", "0", "42"),
                           os.path.join("0_3599", "0", "43")]

    def test_both_stages_start_preloaded_workers_from_one_server(
            self, day_archive, tmp_path, monkeypatch):
        """Each stage starts a worker per non-empty chunk, forked from
        the one preloaded server where the platform offers it, and
        records its workers' start-up once."""
        import multiprocessing
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        _out, c1, t1 = _pipeline_deltas(lambda: gather_traces(
            day_archive, ".*", VALUER, "%Y-%m-%d %H:%M:%S",
            [-90.0, -180.0, 90.0, 180.0], concurrency=4))
        server = _fork_server_pid()
        match_dir = _tile_dir(tmp_path / "matches", ["41", "42", "43"])
        _out, c3, t3 = _pipeline_deltas(lambda: report_tiles(
            match_dir, str(tmp_path / "out"), privacy=1, concurrency=2))
        assert c1["pipeline.worker_starts"] == 2  # two parts
        assert c3["pipeline.worker_starts"] == 2
        assert c3["pipeline.report.tiles"] == 3
        assert not c1["pipeline.gather_failures"]
        assert not c3["pipeline.tile_failures"]
        assert t1["pipeline.worker_boot"] == t3["pipeline.worker_boot"] == 1
        if "forkserver" in multiprocessing.get_all_start_methods():
            for c in (c1, c3):
                assert c["pipeline.worker_starts.preloaded"] \
                    == c["pipeline.worker_starts"]
            assert server is not None and _fork_server_pid() == server


def _text_records(path):
    """(uuid, epoch, lat, lon, accuracy) of each row of a CSV text shard
    file, in file order."""
    with open(path) as f:
        return [(u, int(t), float(la), float(lo), int(a))
                for u, t, la, lo, a in (ln.rstrip("\n").split(",")
                                        for ln in f)]


def _block_records(path):
    """(uuid, epoch, lat, lon, accuracy) of each record of a shard file's
    blocks, in file order."""
    from reporter_tpu.pipeline.simple_reporter import _read_shard
    uuids, recs, codes, torn = _read_shard([str(path)])
    assert torn == 0
    return [(uuids[c], int(r["epoch"]), float(r["lat"]), float(r["lon"]),
             int(r["acc"])) for r, c in zip(recs, codes.tolist())]


def _text_shard_windows(paths, inactivity, holdback_s, options):
    """Stage 2's parse of CSV text shards as it was before the blocks: a
    line at a time, per-uuid lists, a stable argsort per uuid. The oracle
    for ``_shard_windows``."""
    by_uuid = {}
    for path in paths:
        with open(path) as f:
            for line in f:
                try:
                    uuid, tm, lat, lon, acc = line.strip().split(",")
                    cols = by_uuid.get(uuid)
                    if cols is None:
                        cols = by_uuid[uuid] = ([], [], [], [])
                    cols[0].append(int(tm))
                    cols[1].append(float(lat))
                    cols[2].append(float(lon))
                    cols[3].append(int(acc))
                except ValueError:
                    continue
    from reporter_tpu.pipeline.simple_reporter import _window_spans
    parts = []
    for uuid, (tms, lats, lons, accs) in by_uuid.items():
        tm = np.asarray(tms, dtype=np.float64)
        order = np.argsort(tm, kind="stable")
        tm = tm[order]
        la = np.asarray(lats, dtype=np.float64)[order]
        lo_ = np.asarray(lons, dtype=np.float64)[order]
        ac = np.asarray(accs, dtype=np.float32)[order]
        for a, b in _window_spans(tm, inactivity, holdback_s=holdback_s):
            parts.append((uuid, la[a:b], lo_[a:b], tm[a:b], ac[a:b],
                          options))
    return parts


def _probe_part(path, vehicles, rng):
    """A part of pipe-separated lines for ``vehicles`` {uuid: points},
    shuffled across uuids and in time: times repeat (ties), gaps beyond
    30 s split windows, and the 2,500-point vehicle is chunked."""
    lines = []
    for uuid, n in vehicles.items():
        span = n if n > 1024 else 40 * n
        for t in 1_500_000_000 + rng.integers(0, span, n):
            cols = ["x"] * 11
            cols[0], cols[1] = str(t), uuid
            cols[5] = str(rng.choice(["3", "7.5", "1200"]))
            cols[9] = str(40.0 + rng.uniform(0.0, 0.5))
            cols[10] = str(-74.0 + rng.uniform(0.0, 0.5))
            lines.append("|".join(cols))
    rng.shuffle(lines)
    with gzip.open(path, "wt") as f:
        f.write("\n".join(lines))


def _shard_paths(root):
    """{shard: its files, sorted} of a trace dir."""
    by_shard = {}
    for f in sorted(os.listdir(root)):
        by_shard.setdefault(f.split(".")[0], []).append(
            os.path.join(root, f))
    return by_shard


def _parse_counters(fn):
    """(fn's result, its moves of the pipeline.parse.* counters)."""
    from reporter_tpu.utils import metrics
    before = metrics.default.snapshot()["counters"]
    out = fn()
    after = metrics.default.snapshot()["counters"]
    return out, {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("pipeline.parse.probes",
                           "pipeline.parse.torn_blocks")}


def _assert_same_parts(got, want):
    assert [p[0] for p in got] == [p[0] for p in want]
    for g, w in zip(got, want):
        for a, b in zip(g[1:5], w[1:5]):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        assert g[5] is w[5]


class TestShardBlocks:
    """The columnar shard format between stage 1 and stage 2."""

    def _gather(self, parts, dest, per_line):
        """Each part of ``parts`` into ``dest`` as today's gather or the
        per-line text oracle. Of several parts, the first part's files
        are renamed as if another worker wrote them, so a shard has
        files of two workers and, from three parts, a file of several
        parts' blocks."""
        import functools
        from reporter_tpu.pipeline.simple_reporter import (
            DEFAULT_VALUER, _parse_part_file)
        valuer = eval(DEFAULT_VALUER, {"functools": functools})
        parse = _parse_part_file_per_line if per_line else _parse_part_file
        dest.mkdir()
        for k, part in enumerate(parts):
            parse(str(part), valuer, "%Y-%m-%d %H:%M:%S",
                  [-90.0, -180.0, 90.0, 180.0], str(dest))
            if k == 0 and len(parts) > 1:
                for f in os.listdir(dest):
                    if f.endswith(f".{os.getpid()}"):
                        os.rename(dest / f, dest / f.replace(
                            f".{os.getpid()}", ".0"))

    def test_stage_2_parts_equal_the_text_parse(self, tmp_path,
                                                monkeypatch):
        """Value for value and in order: uuids in order of first probe
        over the shard's files, ties in time in file order, lat and lon
        bit for bit, epochs as float64 and accuracies as float32."""
        from reporter_tpu.pipeline.simple_reporter import _shard_windows
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        rng = np.random.default_rng(3)
        vehicles = {f"veh-{i}": 300 for i in range(18)}
        vehicles["veh-long"] = 2500
        shard_of = (lambda u: hashlib.sha1(u.encode()).hexdigest()[:3])
        one = [u for u in (f"fleet-{i}" for i in range(200_000))
               if shard_of(u) == shard_of("veh-long")][:6]
        vehicles.update((u, 300) for u in one)  # seven uuids in one shard
        parts = []
        for k in range(3):
            parts.append(tmp_path / f"part-{k}.gz")
            _probe_part(parts[-1], vehicles, rng)
        self._gather(parts, tmp_path / "text", per_line=True)
        self._gather(parts, tmp_path / "blocks", per_line=False)
        text = _shard_paths(tmp_path / "text")
        blocks = _shard_paths(tmp_path / "blocks")
        assert sorted(text) == sorted(blocks)
        assert [os.path.basename(p) for v in text.values() for p in v] \
            == [os.path.basename(p) for v in blocks.values() for p in v]
        assert any(len(v) > 1 for v in blocks.values())
        opts = {"mode": "auto"}
        n_parts = n_probes = ties = 0
        for shard, paths in blocks.items():
            want = _text_shard_windows(text[shard], 30, 15, opts)
            got, moved = _parse_counters(
                lambda: _shard_windows(paths, 30, 15, opts))
            _assert_same_parts(got, want)
            n_parts += len(got)
            ties += sum(int(np.sum(np.diff(p[3]) == 0)) for p in got)
            n_probes += moved["pipeline.parse.probes"]
            assert moved["pipeline.parse.torn_blocks"] == 0
        assert n_parts > 3 * len(vehicles) and ties > 0
        assert len({p[0] for p in _shard_windows(
            blocks[shard_of("veh-long")], 30, 15, opts)}) == 7
        assert n_probes == 3 * sum(vehicles.values())

    @pytest.mark.parametrize("cut", ["header", "records"])
    def test_a_torn_last_block_is_skipped_and_counted(self, tmp_path,
                                                      monkeypatch, cut):
        """A worker killed while appending its second block leaves the
        first whole: stage 2 reads the first block's probes alone and
        counts one torn block."""
        from reporter_tpu.pipeline.simple_reporter import (
            _HEADER, _shard_windows)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        part = tmp_path / "part-0.gz"
        _probe_part(part, {"veh-torn": 400}, np.random.default_rng(5))
        self._gather([part], tmp_path / "first", per_line=False)
        (first,) = os.listdir(tmp_path / "first")
        whole = (tmp_path / "first" / first).read_bytes()
        torn = tmp_path / "torn"
        # the same block again, cut inside its header or its records
        torn.write_bytes(whole + whole[:_HEADER.size - 5 if cut == "header"
                                       else len(whole) - 7])
        want = _shard_windows([str(tmp_path / "first" / first)], 120, 15,
                              None)
        got, moved = _parse_counters(
            lambda: _shard_windows([str(torn)], 120, 15, None))
        _assert_same_parts(got, want)
        assert moved == {"pipeline.parse.probes": 400,
                         "pipeline.parse.torn_blocks": 1}

    def test_a_csv_trace_dir_must_be_gathered_again(self, tmp_path):
        """A trace dir of CSV text shards, as an earlier build gathered
        it, is refused by name, never misread."""
        trace_dir = tmp_path / "traces"
        trace_dir.mkdir()
        (trace_dir / "abc.1").write_text(
            "veh-1,1500000000,40.5,-74.0,5\nveh-1,1500000001,40.5,-74.0,5\n")

        class Unused:
            def match_many(self, tb):
                raise AssertionError("no batch may reach the matcher")

        with pytest.raises(ValueError, match="abc.1.*gather it again"):
            match_traces(str(trace_dir), Unused(), "auto", {0, 1}, {0, 1},
                         quantisation=3600, inactivity=120, source="test")

    def test_stage_2_reads_every_probe_stage_1_kept(self, day_archive,
                                                    tmp_path, monkeypatch):
        """The format's own consistency check: on a sound run
        ``pipeline.parse.probes`` equals ``pipeline.gather.probes`` and no
        block is torn."""
        from reporter_tpu.utils import metrics
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        before = metrics.default.snapshot()["counters"]
        trace_dir = gather_traces(day_archive, ".*", VALUER,
                                  "%Y-%m-%d %H:%M:%S",
                                  [-90.0, -180.0, 90.0, 180.0],
                                  concurrency=2)
        seen = []

        class Recorder:
            def match_many(self, tb):
                seen.extend(int(n) for n in tb.lengths())
                raise RuntimeError("recorded")

        match_traces(trace_dir, Recorder(), "auto", {0, 1}, {0, 1},
                     quantisation=3600, inactivity=120, source="test")
        after = metrics.default.snapshot()["counters"]
        c = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("pipeline.gather.probes", "pipeline.parse.probes",
                       "pipeline.parse.torn_blocks")}
        assert c["pipeline.parse.probes"] == c["pipeline.gather.probes"] > 0
        assert c["pipeline.parse.torn_blocks"] == 0
        assert sum(seen) >= c["pipeline.parse.probes"]

    def test_a_uuid_with_a_comma_survives_both_stages(self, city, tmp_path,
                                                      monkeypatch):
        """A uuid is kept whole through the gather and stage 2, whatever
        it holds; the CSV shards split it at its comma and lost it."""
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("REPORTER_TPU_NUM_PROCESSES", raising=False)
        src = tmp_path / "src"
        src.mkdir()
        lines = make_part_file(city, str(src / "part-0.gz"), n_traces=2,
                               seed=9)
        odd = "fleet,7 \u00e9"
        with gzip.open(src / "part-0.gz", "wt") as f:
            f.write("\n".join(ln.replace("veh-9-1", odd) for ln in lines))
        trace_dir = gather_traces(str(src), ".*", VALUER,
                                  "%Y-%m-%d %H:%M:%S",
                                  [-90.0, -180.0, 90.0, 180.0],
                                  concurrency=1)
        seen = {}

        class Recorder:
            def match_many(self, tb):
                for i in range(len(tb)):
                    seen[tb.uuid(i)] = seen.get(tb.uuid(i), 0) \
                        + int(tb.lengths()[i])
                raise RuntimeError("recorded")

        match_traces(trace_dir, Recorder(), "auto", {0, 1}, {0, 1},
                     quantisation=3600, inactivity=120, source="test")
        n_odd = sum(ln.split("|")[1] == "veh-9-1" for ln in lines)
        assert set(seen) == {"veh-9-0", odd}
        assert seen[odd] == n_odd > 0
