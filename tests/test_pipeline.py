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
