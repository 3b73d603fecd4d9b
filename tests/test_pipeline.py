"""Batch pipeline tests: gather -> batched match -> tile report, end to end
over local files (the reference's S3 path is gated off in this image)."""
import gzip
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
        """One process per chip: stage 3's spawned workers run after
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


def _run_stages(archive, city, device_batch, dest, matcher=None):
    """Stages 1-3 over ``archive`` -> (stage-2 dir, counter deltas,
    timer count deltas)."""
    from reporter_tpu.utils import metrics
    before = metrics.default.snapshot()
    trace_dir = gather_traces(archive, ".*", VALUER, "%Y-%m-%d %H:%M:%S",
                              [-90.0, -180.0, 90.0, 180.0], concurrency=2)
    match_dir = match_traces(
        trace_dir, matcher or SegmentMatcher(net=city), "auto", {0, 1, 2},
        {0, 1, 2}, quantisation=3600, inactivity=120, source="test",
        device_batch=device_batch)
    report_tiles(match_dir, str(dest), privacy=1, concurrency=2)
    after = metrics.default.snapshot()
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()
                if k.startswith("pipeline.")}
    timers = {k: v["count"] - before["timers"].get(k, {"count": 0})["count"]
              for k, v in after["timers"].items() if k.startswith("pipeline.")}
    return match_dir, counters, timers


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
    """The gather's parse as it was before its memos: a shard hash, a
    host verdict and a calendar epoch for every line. The oracle for
    ``_parse_part_file``'s shard files."""
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
    part; the shard files are the per-line parse's, byte for byte."""

    @pytest.mark.parametrize("hosts", ["one", "two"])
    @pytest.mark.parametrize("time_pattern",
                             ["%Y-%m-%d %H:%M:%S", "%d/%m/%Y %H:%M:%S"])
    def test_shard_files_match_the_per_line_parse(self, tmp_path,
                                                  monkeypatch, hosts,
                                                  time_pattern):
        import functools
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
            part, valuer, time_pattern, bbox, str(new))
        want = {f: (old / f).read_bytes() for f in os.listdir(old)}
        got = {f: (new / f).read_bytes() for f in os.listdir(new)}
        assert want and got == want
        assert n_lines == len(lines)
        assert probes == sum(b.count(b"\n") for b in want.values())
        kept = {b.split(b",")[0] for v in want.values()
                for b in v.splitlines()}
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
            f"{str(dest)!r}, send)\n"
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
            rows = []
            for r, _d, fs in os.walk(out):
                for f in fs:
                    with open(os.path.join(r, f)) as fh:
                        rows += fh.readlines()
            rows.sort()
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
