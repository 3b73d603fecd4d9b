"""The plain reference of the streaming worker: a probe stream replayed
under stream-time punctuation into anonymised tile rows.

Copies of the worker's logic (``reporter_tpu/streaming``: the formatter,
``batcher.py``, ``anonymiser.py`` and ``worker.py``'s punctuation, from
the reference's ``Batch.java``, ``BatchingProcessor.java`` and
``AnonymisingProcessor.java``), importing nothing of ``reporter_tpu``:

- a message ``uuid|lat|lon|epoch seconds|accuracy`` becomes a point
  with its latitude and longitude rounded to float32 and its accuracy
  rounded up;
- stream time is the largest probe time offered so far; the first probe
  starts the three schedules below;
- per uuid a window collects points and the largest distance from its
  first point; it is due once it spans >= 500 m, >= 10 points and
  >= 60 s. Due windows wait in a pending set, flushed when it holds 64,
  and at the first message at least ``report_flush_interval_s`` of
  stream time after the last flush that found it non-empty;
- every 2 x 60 s of stream time, windows idle for more than 60 s are
  evicted and reported if they hold >= 2 points (0 m, 0 s), in the same
  flush as the pending ones; the drain evicts every window;
- each window is decoded whole (``report``, one ``ref.pipeline``
  answer): the incremental path's own claim is that it answers what the
  whole window's decode answers. The window is then trimmed at the
  answer's ``shape_used``; without one, it is kept whole where segments
  matched and emptied where none did (``Batch.apply_response``);
- each valid report (t0, t1 > 0, t1 > t0, length > 0, queue >= 0)
  becomes a (segment, next segment) observation in every hour bucket
  of [int(t0), int(t1)] of its graph tile;
- every ``flush_interval_s`` of stream time, and at the drain, each
  tile's observations are sorted by (segment, next segment), runs of a
  pair shorter than ``privacy`` are dropped, and the rest are written
  as CSV rows to ``{t}_{t + q - 1}/{level}/{tile index}`` under the
  name ``{source}.e{flush epoch:08d}``; every flush takes an epoch.

:meth:`StreamReference.run` returns both the rows observed before the
cull, each under the file its flush writes, and the rows the files
keep.
"""
from __future__ import annotations

import math

import numpy as np

from .geo import equirectangular_m
from .osmlr import INVALID_SEGMENT_ID, LEVEL_BITS, LEVEL_MASK, \
    TILE_INDEX_BITS, TILE_INDEX_MASK

REPORT_TIME = 60        # seconds (BatchingProcessor.java:26)
REPORT_COUNT = 10       # points  (:27)
REPORT_DIST = 500       # meters  (:28)
SESSION_GAP_MS = 60000  # ms      (:29)
#: pending windows that force a flush (the batcher's ``report_flush``)
PENDING_CAP = 64


def parse(line: str) -> tuple:
    """(uuid, lat, lon, accuracy, time) of one ``|``-separated message,
    latitude and longitude rounded to float32."""
    c = line.rstrip("\r\n").split("|")
    lat, lon = (float(x) for x in np.float32([float(c[1]), float(c[2])]))
    return c[0], lat, lon, int(math.ceil(float(c[4]))), int(c[3])


class _Window:
    __slots__ = ("points", "max_sep", "last_update")

    def __init__(self, point: tuple):
        self.points = [point]
        self.max_sep = 0.0
        self.last_update = 0

    def add(self, p: tuple) -> None:
        if self.points:
            first = self.points[0]
            self.max_sep = max(self.max_sep, equirectangular_m(
                p[0], p[1], first[0], first[1]))
        self.points.append(p)

    def due(self, dist: float, size: int, elapsed: float) -> bool:
        return not (self.max_sep < dist or len(self.points) < size
                    or self.points[-1][3] - self.points[0][3] < elapsed)

    def trim(self, body: dict) -> None:
        sm = body.get("segment_matcher")
        if "shape_used" in body:
            cut = body["shape_used"]
        elif sm and sm.get("segments"):
            cut = 0
        else:
            cut = len(self.points)
        del self.points[:cut]
        self.max_sep = 0.0
        if len(self.points) > 1:
            lat = np.array([p[0] for p in self.points])
            lon = np.array([p[1] for p in self.points])
            self.max_sep = float(np.max(equirectangular_m(
                lat[1:], lon[1:], lat[0], lon[0])))


def csv_row(sid: int, nxt: int, t0: float, t1: float, length: int,
            queue: int, mode: str, source: str) -> str:
    """One tile row of an observation (``Segment.csv_row``)."""
    return ",".join([
        str(sid), "" if nxt == INVALID_SEGMENT_ID else str(nxt),
        str(int(math.floor(t1 - t0 + 0.5))), "1", str(length), str(queue),
        str(int(math.floor(t0))), str(int(math.ceil(t1))), source, mode])


class StreamReference:
    """The worker's stream-time replay. ``reports(requests)`` answers a
    flush's windows (``/report`` request dicts), in order."""

    def __init__(self, reports, privacy: int, quantisation: int,
                 flush_interval_s: float, report_flush_interval_s: float,
                 mode: str, source: str, report_levels,
                 transition_levels):
        self.reports = reports
        self.privacy = privacy
        self.quantisation = quantisation
        self.flush_interval_s = flush_interval_s
        self.report_flush_interval_s = report_flush_interval_s
        self.mode = mode.upper()
        self.source = source
        self.options = {"mode": mode,
                        "report_levels": list(report_levels),
                        "transition_levels": list(transition_levels)}
        self.store = {}
        self.pending = {}
        self.tiles = {}       # (bucket start, tile id) -> [(key, row, t0, t1)]
        # (tile dir, file name, row, t0, t1) of every observation, and of
        # those the cull kept
        self.observed = []
        self.rows = []
        self.epoch = 0
        self.windows = 0
        self.now = None
        self.last_report = self.last_evict = self.last_tiles = 0.0

    def run(self, lines) -> tuple:
        """(the rows observed before the cull, the tile files' rows), each
        as (tile dir, file name, row, t0, t1)."""
        for line in lines:
            self.offer(line)
        self._evict(int((self.now or 0.0) * 1000) + 10 * SESSION_GAP_MS)
        self._flush_tiles()
        return self.observed, self.rows

    def offer(self, line: str) -> None:
        uuid, lat, lon, acc, t = parse(line)
        if self.now is None:
            self.now = self.last_report = self.last_evict = \
                self.last_tiles = float(t)
        self.now = max(self.now, float(t))
        self._process(uuid, (lat, lon, acc, t), int(self.now * 1000))
        now = self.now
        if self.pending and now - self.last_report \
                >= self.report_flush_interval_s:
            self._flush_pending()
            self.last_report = now
        if (now - self.last_evict) * 1000 >= 2 * SESSION_GAP_MS:
            self._evict(int(now * 1000))
            self.last_evict = now
        if now - self.last_tiles >= self.flush_interval_s:
            self._flush_tiles()
            self.last_tiles = now

    def _process(self, uuid: str, point: tuple, now_ms: int) -> None:
        w = self.store.pop(uuid, None)
        if w is None:
            w = _Window(point)
        else:
            w.add(point)
            if w.due(REPORT_DIST, REPORT_COUNT, REPORT_TIME):
                self.pending[uuid] = None
        w.last_update = now_ms
        self.store[uuid] = w
        if len(self.pending) >= PENDING_CAP:
            self._flush_pending()

    def _live_due(self) -> list:
        due = [(u, self.store[u]) for u in self.pending if u in self.store
               and self.store[u].due(REPORT_DIST, REPORT_COUNT,
                                     REPORT_TIME)]
        self.pending.clear()
        return due

    def _flush_pending(self) -> None:
        self._report(self._live_due())

    def _evict(self, now_ms: int) -> None:
        due = []
        for uuid in list(self.store):
            w = self.store[uuid]
            if now_ms - w.last_update > SESSION_GAP_MS:
                del self.store[uuid]
                self.pending.pop(uuid, None)
                if w.due(0, 2, 0):
                    due.append((uuid, w))
        self._report(due + self._live_due())

    def _report(self, due: list) -> None:
        if not due:
            return
        requests = [{"uuid": uuid, "match_options": self.options,
                     "trace": [{"lat": round(p[0], 6), "lon": round(p[1], 6),
                                "time": p[3], "accuracy": p[2]}
                               for p in w.points]}
                    for uuid, w in due]
        for (uuid, w), body in zip(due, self.reports(requests)):
            self.windows += 1
            w.trim(body)
            for r in body["datastore"]["reports"]:
                self._observe(r)

    def _observe(self, r: dict) -> None:
        sid = int(r["id"])
        nxt = r.get("next_id")
        nxt = INVALID_SEGMENT_ID if nxt is None else int(nxt)
        t0, t1 = float(r["t0"]), float(r["t1"])
        length, queue = int(r["length"]), int(r["queue_length"])
        if not (t0 > 0 and t1 > 0 and t1 > t0 and length > 0
                and queue >= 0):
            return
        tile = sid & ((1 << (LEVEL_BITS + TILE_INDEX_BITS)) - 1)
        row = csv_row(sid, nxt, t0, t1, length, queue, self.mode,
                      self.source)
        q = self.quantisation
        for b in range(int(t0) // q, int(t1) // q + 1):
            self.tiles.setdefault((b * q, tile), []).append(
                ((sid, nxt), row, t0, t1))
            self.observed.append((self._tile_dir(b * q, tile),
                                  self._file_name(), row, t0, t1))

    def _tile_dir(self, start: int, tile: int) -> str:
        return (f"{start}_{start + self.quantisation - 1}/"
                f"{tile & LEVEL_MASK}/"
                f"{(tile >> LEVEL_BITS) & TILE_INDEX_MASK}")

    def _file_name(self) -> str:
        """The file the next flush writes a tile's rows to."""
        return f"{self.source}.e{self.epoch:08d}"

    def _flush_tiles(self) -> None:
        name = self._file_name()
        for (start, tile), obs in self.tiles.items():
            obs = sorted(obs, key=lambda o: o[0])
            kept = []
            i = 0
            while i < len(obs):
                j = i
                while j < len(obs) and obs[j][0] == obs[i][0]:
                    j += 1
                if j - i >= self.privacy:
                    kept += obs[i:j]
                i = j
            self.rows += [(self._tile_dir(start, tile), name, row, t0, t1)
                          for _k, row, t0, t1 in kept]
        self.tiles = {}
        self.epoch += 1

