"""The batched (historical) pipeline: raw probe archives -> traffic tiles.

Capability-parity rebuild of the reference's script pipeline
(reference: py/simple_reporter.py) with the matching stage redesigned for
the TPU. Same three stages, same artifacts:

1. **gather_traces** — list + download part files (S3 via boto3 when
   configured and available, else a local directory), parse each line with
   a user-supplied ``--src-valuer`` lambda, bbox-filter, cap accuracy at
   1000 m, and shard probes into files by ``sha1(uuid)[:3]``
   (reference: :87-129, :256-276). IO-bound -> process fan-out.

2. **match_traces** — per shard: group by uuid, sort by time, split into
   windows at ``--inactivity`` gaps (reference: :149-163). Stage 2 reads
   the gather's typed values as arrays; it parses no text (below). THE
   redesign:
   instead of one C++ ``Match`` call per window (reference: :164-168 — the
   hot loop), *windows go to the device ``--device-batch`` at a time,
   pooled across shards,* via ``SegmentMatcher.match_many``; ``report()``
   post-processing and the usable-segment filter are unchanged
   (:176-177), and rows land
   in ``{bucket_start}_{bucket_end}/{level}/{tile_index}`` files (:178-196).

3. **report_tiles** — per tile file: sort, privacy-cull (id, next_id) runs
   below ``--privacy`` observations, prepend the CSV header, upload/write
   (reference: :211-254).

Stage-level resume via --trace-dir / --match-dir is preserved
(reference: :350-363). Each stage times itself (``pipeline.gather``,
``pipeline.match``, ``pipeline.report``; inside stage 2
``pipeline.parse``, ``pipeline.match_many`` and ``pipeline.rows``) and
counts its windows, points, batches, rows and failures (``pipeline.*``
counters), so the CLI and any other caller time the same code.

**Shard files** (the trace dir between stages 1 and 2; the reference's
are CSV text) are named ``{shard}.{pid}``: one per shard and gather
worker. Each part a worker gathers appends one block to each shard file
it has probes for, whole, once the part is parsed, so a part that raises
writes nothing and a worker killed after k parts leaves k whole blocks
and at most one torn one at the end. A block, little-endian:

- header, 32 bytes: the tag ``BLOCK_TAG`` (``RTSHARD`` and a version
  byte, 1), then uint64 uuid count, record count and uuid-table bytes;
- uuid table: int32 UTF-8 byte length of each uuid, then the uuids'
  UTF-8 bytes, zero-padded to a multiple of 8 bytes; a uuid's code is
  its index, in order of its first record in the block;
- records, 32 bytes each, in input order: epoch int64, lat float64,
  lon float64, accuracy int32, uuid code int32.

Stage 2 skips and counts a torn block (``pipeline.parse.torn_blocks``)
and refuses a file that holds anything else, such as a CSV trace dir an
earlier build gathered: gather that again. ``pipeline.parse.probes``
equals ``pipeline.gather.probes`` on a sound run.
"""
from __future__ import annotations

import argparse
import calendar
import functools
import glob
import gzip
import hashlib
import logging
import math
import multiprocessing
import os
import re
import shutil
import struct
import sys
import tempfile
import time
from typing import Callable, Iterable, List

from ..core.osmlr import INVALID_SEGMENT_ID, tile_index, tile_level
from ..core.types import Segment
from ..parallel.multihost import host_share, host_uuid_filter
from ..utils import metrics

logger = logging.getLogger("reporter_tpu.pipeline")

# The stages' worker processes (_run_workers) are never forked from the
# caller, which may already run JAX's (and jax.distributed's) native
# threads: forking a multi-threaded process can deadlock the child
# mid-mutex. Where the platform has one they are forked from a fork
# server, a fresh interpreter (fork and exec, like spawn) that imports
# this module, and numpy with it, once, so a worker starts in
# milliseconds; elsewhere each is spawned and imports it itself. The
# server preloads this module alone, not __main__, whose script may
# import JAX at its top level: it initialises no XLA backend. It starts
# with a process's first worker and serves every later stage and job.
# The preload is named in full: run with -m, this module is __main__.
if "forkserver" in multiprocessing.get_all_start_methods():
    _MP = multiprocessing.get_context("forkserver")
    _MP.set_forkserver_preload(["reporter_tpu.pipeline.simple_reporter"])
else:
    _MP = multiprocessing.get_context("spawn")
# the process that imported this module: a worker forked from the
# preloaded server finds the server's pid here, not its own
_LOADED_IN = os.getpid()

MAX_ACCURACY_M = 1000  # reference: simple_reporter.py:112

# the shard block (module docstring); the tag's last byte is the version
BLOCK_TAG = b"RTSHARD\x01"
_HEADER = struct.Struct("<8sQQQ")  # tag, uuids, records, uuid-table bytes
_RECORD = struct.Struct("<qddi")  # epoch, lat, lon, accuracy ...
_CODE = struct.Struct("<i")  # ... then the uuid's code in the block
RECORD_BYTES = _RECORD.size + _CODE.size


class _Block:
    """One part's records for one shard, as the gather appends them."""

    __slots__ = ("uuids", "records")

    def __init__(self):
        self.uuids: list[str] = []  # code -> uuid, in order of first record
        self.records = bytearray()

    def encode(self) -> bytes:
        names = [u.encode() for u in self.uuids]
        table = struct.pack(f"<{len(names)}i", *map(len, names)) \
            + b"".join(names)
        table += bytes(-len(table) % 8)  # records start 8-byte aligned
        return _HEADER.pack(BLOCK_TAG, len(names),
                            len(self.records) // RECORD_BYTES,
                            len(table)) + table + self.records


# --------------------------------------------------------------------------
# stage 1: gather
# --------------------------------------------------------------------------

def _parse_part_file(path: str, valuer: Callable, time_pattern: str,
                     bbox: List[float], dest_dir: str,
                     uuid_filter: Callable | None = None) -> tuple:
    """Parse one downloaded part file into uuid-sharded trace files: one
    columnar block appended to each shard file it has probes for. Only
    the uuids ``uuid_filter`` keeps are gathered (all where it is None).

    Returns ``(lines, probes, uuid_hashes, hour_epochs)``: lines read,
    probes kept, distinct uuids hashed and hour epochs computed. A part
    holds thousands of lines per uuid and per hour, so a uuid's shard
    (and its host verdict) and an hour's epoch are computed once and
    looked up after that."""
    fast_time = time_pattern == "%Y-%m-%d %H:%M:%S"
    opener = gzip.open if path.endswith(".gz") else open
    shards: dict[str, _Block] = {}
    # str(uuid) -> [its shard's block, its code there packed, or None until
    # its first record], or None where another host owns it
    block_of: dict[str, list | None] = {}
    hour_epoch: dict[str, int] = {}  # "YYYY-MM-DD HH" -> epoch of the hour
    lines = count = 0
    with opener(path, "rt") as f:
        for line in f:
            lines += 1
            try:
                uuid, tm, lat, lon, acc = valuer(line)
                key = str(uuid)
                try:
                    entry = block_of[key]
                except KeyError:
                    entry = None
                    if uuid_filter is None or uuid_filter(key):
                        shard = hashlib.sha1(key.encode()).hexdigest()[:3]
                        block = shards.get(shard)
                        if block is None:
                            block = shards[shard] = _Block()
                        entry = [block, None]
                    block_of[key] = entry
                if entry is None:
                    continue
                lat = float(lat)
                lon = float(lon)
                if lat < bbox[0] or lat > bbox[2] or \
                        lon < bbox[1] or lon > bbox[3]:
                    continue
                if isinstance(tm, str) and not tm.isdigit():
                    if fast_time:
                        # timegm is linear in minutes and seconds
                        hour = tm[:13]
                        epoch = hour_epoch.get(hour)
                        if epoch is None:
                            epoch = hour_epoch[hour] = calendar.timegm((
                                int(tm[0:4]), int(tm[5:7]), int(tm[8:10]),
                                int(tm[11:13]), 0, 0))
                        epoch += int(tm[14:16]) * 60 + int(tm[17:19])
                    else:
                        epoch = calendar.timegm(
                            time.strptime(tm, time_pattern))
                else:
                    epoch = int(tm)
                acc = min(int(math.ceil(float(acc))), MAX_ACCURACY_M)
                # struct.error where a value does not fit its column
                record = _RECORD.pack(epoch, lat, lon, acc)
            except Exception:
                continue
            block, code = entry
            if code is None:
                # codes follow first records, so the uuid table lists
                # exactly the uuids the block's records name
                code = entry[1] = _CODE.pack(len(block.uuids))
                block.uuids.append(key)
            block.records += record
            block.records += code
            count += 1
    # one shard file per worker process (suffix = pid): concurrent gather
    # workers never share a file, so no interleaved/torn blocks — stage 2
    # walks every file in the directory regardless of suffix
    pid = os.getpid()
    for shard, block in shards.items():
        if block.uuids:  # a shard whose uuids kept no probe gets no file
            with open(os.path.join(dest_dir, f"{shard}.{pid}"), "ab") as f:
                f.write(block.encode())
    return lines, count, len(block_of), len(hour_epoch)


def _gather_worker(paths: List[str], valuer_src: str, time_pattern: str,
                   bbox: List[float], dest_dir: str, hosts: tuple,
                   results) -> None:
    """Parse ``paths`` in turn, keeping the uuids of the host share
    ``hosts`` (``(num_processes, process_id)``), and send each part's
    tally (or None where the part raised) down the ``results`` pipe."""
    valuer = eval(valuer_src)  # user-supplied lambda, like the reference
    uuid_filter = host_uuid_filter(*hosts)
    for path in paths:
        try:
            tally = _parse_part_file(path, valuer, time_pattern, bbox,
                                     dest_dir, uuid_filter)
            logger.info("Gathered %d probes from %s", tally[1], path)
        except Exception as e:
            tally = None
            logger.error("%s was not processed: %s", path, e)
        results.send(tally)


def gather_traces(src: str, key_regex: str, valuer_src: str,
                  time_pattern: str, bbox: List[float],
                  concurrency: int) -> str:
    """Stage 1 driver. ``src`` is a local directory of part files, or an
    ``s3://bucket/prefix`` URL (requires boto3 + credentials).

    Multi-host backfill: where REPORTER_TPU_NUM_PROCESSES/PROCESS_ID are
    set, each host keeps only its share of the uuid space, so N hosts
    pointed at the same ``src`` partition the work instead of repeating
    it (the reference splits days across instances by hand,
    load-historical-data/README.md). The share is read here, at the call,
    and handed to the workers.

    Counts ``pipeline.gather.lines``, ``.probes``, ``.uuid_hashes`` and
    ``.hour_epochs`` over the parts, and ``pipeline.gather_failures`` for
    each part that raised or whose worker died before reporting it."""
    with metrics.timer("pipeline.gather"):
        hosts = host_share()
        dest_dir = tempfile.mkdtemp(prefix="traces_", dir=".")
        if src.startswith("s3://"):
            paths = _download_s3(src, key_regex)
        else:
            rx = re.compile(key_regex)
            paths = sorted(
                p for p in glob.glob(os.path.join(src, "**", "*"),
                                     recursive=True)
                if os.path.isfile(p) and rx.match(os.path.relpath(p, src)))
        logger.info("Gathering %d part files into %s", len(paths), dest_dir)
        chunks = [paths[i::concurrency] for i in range(concurrency)]
        tallies = _run_workers(_gather_worker, chunks, valuer_src,
                               time_pattern, bbox, dest_dir, hosts)
        done = [t for t in tallies if t is not None]
        lines, probes, hashes, hours = (
            [sum(col) for col in zip(*done)] or [0, 0, 0, 0])
        metrics.count("pipeline.gather.lines", lines)
        metrics.count("pipeline.gather.probes", probes)
        metrics.count("pipeline.gather.uuid_hashes", hashes)
        metrics.count("pipeline.gather.hour_epochs", hours)
        metrics.count("pipeline.gather_failures", len(paths) - len(done))
    return dest_dir


def _run_workers(target: Callable, chunks: List[list], *args) -> list:
    """Run ``target(chunk, *args, results)`` in a worker process for each
    non-empty chunk of ``chunks``; ``target`` sends one tally down the
    one-way pipe ``results`` for each item of its chunk, in order.
    Returns the tallies of all chunks, in order: one per item, None for
    each item whose worker died before it reported it.

    Counts ``pipeline.worker_starts`` and, of those, the workers forked
    from the preloaded server (``pipeline.worker_starts.preloaded``), and
    records ``pipeline.worker_boot``: from the first start to the last
    worker entering ``target``."""
    env = dict(os.environ)
    workers = []
    t_start = time.monotonic()
    for chunk in chunks:
        if not chunk:
            continue
        recv, send = _MP.Pipe(duplex=False)
        p = _MP.Process(target=_enter,
                        args=(target, (chunk, *args), env, send))
        p.start()
        send.close()  # the child's end: recv sees EOF once it exits
        workers.append((p, recv, len(chunk)))
    metrics.count("pipeline.worker_starts", len(workers))
    tallies, entered, preloaded = [], [], 0
    for p, recv, n in workers:
        sent = []
        with recv:
            while True:
                try:
                    sent.append(recv.recv())
                except EOFError:
                    break
        p.join()
        if sent:
            t_enter, from_server = sent.pop(0)
            entered.append(t_enter)
            preloaded += from_server
        if len(sent) < n:
            logger.error("A worker exited (code %s) before it reported %d "
                         "of its %d items", p.exitcode, n - len(sent), n)
        tallies += sent + [None] * (n - len(sent))
    metrics.count("pipeline.worker_starts.preloaded", preloaded)
    if entered:
        metrics.observe("pipeline.worker_boot", max(entered) - t_start)
    return tallies


def _enter(target: Callable, args: tuple, env: dict, results) -> None:
    """A worker's first steps: adopt the caller's environment, send the
    time it enters ``target`` and whether it was forked from the
    preloaded server, then run ``target(*args, results)``. A worker
    forked from the server starts with the server's environment, as it
    was when the server started; what the worker's code reads of it
    (boto3's credential chain, urllib's proxies) must be the caller's."""
    os.environ.clear()
    os.environ.update(env)
    results.send((time.monotonic(), _LOADED_IN != os.getpid()))
    target(*args, results)


def _download_s3(url: str, key_regex: str) -> List[str]:
    try:
        import boto3
    except ImportError:
        raise RuntimeError("s3 source requires boto3, which is unavailable")
    bucket, _, prefix = url[len("s3://"):].partition("/")
    client = boto3.client("s3")
    rx = re.compile(key_regex)
    keys = []
    token = None
    while True:
        kw = {"Bucket": bucket, "Prefix": prefix}
        if token:
            kw["ContinuationToken"] = token
        resp = client.list_objects_v2(**kw)
        keys.extend(o["Key"] for o in resp.get("Contents", []))
        token = resp.get("NextContinuationToken")
        if not token:
            break
    keys = [k for k in keys if rx.match(k)]
    paths = []
    dl_dir = tempfile.mkdtemp(prefix="parts_", dir=".")
    for key in keys:
        path = os.path.join(dl_dir, hashlib.sha1(key.encode()).hexdigest())
        client.download_file(bucket, key, path)
        paths.append(path)
    return paths


# --------------------------------------------------------------------------
# stage 2: match (batched on device)
# --------------------------------------------------------------------------

# longest window sent to the matcher in one request: the largest padding
# bucket (batchpad.LENGTH_BUCKETS[-1]); longer active windows are chunked
# with a trailing-holdback overlap instead of being truncated
MAX_WINDOW_POINTS = 1024


def _window_spans(times, inactivity: int,
                  max_window: int = MAX_WINDOW_POINTS,
                  holdback_s: int = 15) -> Iterable[tuple]:
    """Index spans ``(lo, hi)`` of matcher windows over a sorted times
    array: split at gaps > ``inactivity`` seconds (reference:
    simple_reporter.py:149-163), then chunk long windows with a
    trailing-holdback overlap (see :func:`_windows_of`). Operating on
    index spans keeps the columnar pipeline zero-copy: each window is a
    slice of the uuid's coordinate arrays, never a list of point dicts.
    """
    import numpy as np

    times = np.asarray(times, dtype=np.float64)
    n = len(times)

    def chunked(start: int, end: int) -> Iterable[tuple]:
        while end - start > max_window:
            yield (start, start + max_window)
            end_t = times[start + max_window - 1]
            j = max_window - 1
            while j > 0 and end_t - times[start + j] <= holdback_s:
                j -= 1
            # progress floor: a pathological burst (>max_window points
            # inside one holdback span) must not degrade to 1-point steps
            # and ~N chunks; advancing at least half a window caps the
            # re-presented overlap at 2x total work
            j = max(max_window // 2, min(j, max_window - 1))
            start += j
        if end - start >= 2:
            yield (start, end)

    gap_at = np.flatnonzero(np.diff(times) > inactivity) + 1
    lo = 0
    for g in gap_at.tolist() + [n]:
        if g - lo >= 2:
            yield from chunked(lo, g)
        lo = g


def _windows_of(points: List[dict], inactivity: int,
                max_window: int = MAX_WINDOW_POINTS,
                holdback_s: int = 15) -> Iterable[List[dict]]:
    """Split a uuid's points at gaps > ``inactivity`` seconds
    (reference: simple_reporter.py:149-163).

    Windows longer than ``max_window`` (the device's largest padding
    bucket) are further split into chunks whose overlap covers the last
    ``holdback_s`` seconds of the previous chunk — the same consumed-prefix
    overlap the streaming path gets from ``shape_used`` trimming
    (reference: Batch.java:73-76, reporter_service.py:89-92): report()
    withholds segments inside the trailing holdback, and the next chunk
    re-presents those points, so pairs at the seam are reported exactly
    once with match context preserved. (Dict-list convenience wrapper
    over :func:`_window_spans`, which the columnar stage uses directly.)
    """
    times = [p["time"] for p in points]
    for lo, hi in _window_spans(times, inactivity, max_window, holdback_s):
        yield points[lo:hi]


def _read_shard(paths: List[str]) -> tuple:
    """Every whole block of a shard's files, in the order given:
    ``(uuids, records, codes, torn)``. ``records`` is a structured array
    (epoch, lat, lon, acc, code) in file order, ``codes`` each record's
    index into ``uuids``, which lists the shard's uuids in order of
    first record, and ``torn`` the blocks cut short at a file's end (a
    gather worker killed mid-write), which are skipped.

    Raises ValueError for a file that holds anything but blocks, such as
    a CSV trace dir an earlier build gathered."""
    import numpy as np

    rec_dtype = np.dtype([("epoch", "<i8"), ("lat", "<f8"), ("lon", "<f8"),
                          ("acc", "<i4"), ("code", "<i4")])
    code_of: dict[str, int] = {}
    records, codes = [], []
    torn = 0
    for path in paths:
        with open(path, "rb") as f:
            buf = f.read()
        off = 0
        while off < len(buf):
            head = buf[off:off + _HEADER.size]
            if head[:len(BLOCK_TAG)] != BLOCK_TAG[:len(head)]:
                raise ValueError(
                    f"{path} holds no shard block at byte {off}: the trace "
                    f"dir was not gathered by this build; gather it again")
            if len(head) < _HEADER.size:
                torn += 1
                break
            _tag, n_uuids, n_records, table_bytes = _HEADER.unpack(head)
            start = off + _HEADER.size
            off = start + table_bytes + n_records * RECORD_BYTES
            if off > len(buf):
                torn += 1
                break
            remap = np.empty(n_uuids, np.int32)
            pos = start + 4 * n_uuids
            for i, n in enumerate(struct.unpack_from(f"<{n_uuids}i", buf,
                                                     start)):
                name = buf[pos:pos + n].decode()
                remap[i] = code_of.setdefault(name, len(code_of))
                pos += n
            recs = np.frombuffer(buf, rec_dtype, n_records,
                                 start + table_bytes)
            records.append(recs)
            codes.append(remap[recs["code"]])
    if not records:
        return [], np.zeros(0, rec_dtype), np.zeros(0, np.int32), torn
    return (list(code_of), np.concatenate(records), np.concatenate(codes),
            torn)


def _shard_windows(paths: List[str], inactivity: int, holdback_s: int,
                   options: dict) -> list:
    """One shard's matcher windows as columnar parts (uuid, lat, lon,
    time, accuracy, options) over array slices: uuids in order of first
    record, each uuid's points in time order, ties in file order.

    One stable sort of the shard's records by (uuid, time), then each
    uuid's run is cut into windows. The chunker's holdback must equal
    report()'s threshold: report withholds segments starting within
    threshold_sec of a chunk's end, and the next chunk re-presents
    exactly that span. Counts ``pipeline.parse.probes`` and
    ``pipeline.parse.torn_blocks``."""
    import numpy as np

    uuids, records, codes, torn = _read_shard(paths)
    metrics.count("pipeline.parse.probes", len(codes))
    if torn:
        metrics.count("pipeline.parse.torn_blocks", torn)
        logger.error("Skipped %d torn blocks in %s", torn, paths)
    if not len(codes):
        return []
    order = np.lexsort((records["epoch"], codes))
    codes = codes[order]
    tm = records["epoch"][order].astype(np.float64)
    la = records["lat"][order]
    lo = records["lon"][order]
    ac = records["acc"][order].astype(np.float32)
    cuts = (np.flatnonzero(np.diff(codes)) + 1).tolist()
    parts = []
    for a, b in zip([0] + cuts, cuts + [len(codes)]):
        uuid = uuids[codes[a]]
        for lo_, hi_ in _window_spans(tm[a:b], inactivity,
                                      holdback_s=holdback_s):
            s = slice(a + lo_, a + hi_)
            parts.append((uuid, la[s], lo[s], tm[s], ac[s], options))
    return parts


def match_traces(trace_dir: str, matcher, mode: str,
                 report_levels: set, transition_levels: set,
                 quantisation: int, inactivity: int, source: str,
                 threshold_sec: int = 15,
                 device_batch: int = 512) -> str:
    """Stage 2 driver: shard files -> batched device matching -> tile rows.

    ``matcher`` is a SegmentMatcher (or anything with ``match_many``).
    Shards are cut into windows one at a time, but the windows are
    pooled ACROSS shards: each ``match_many`` call gets ``device_batch``
    windows (the last call what is left), so the device sees full
    batches however few vehicles a shard holds (a metro day puts about
    one vehicle in each of the 4,096 shards). A failed batch or report is
    logged and counted (``pipeline.batch_failures``,
    ``pipeline.report_failures``), never silently dropped.
    """
    with metrics.timer("pipeline.match"):
        dest_dir = tempfile.mkdtemp(prefix="matches_", dir=".")
        # gather workers write one file per (shard, worker pid); all files
        # with the same sha1-prefix shard belong together so a uuid's
        # points are consolidated no matter which worker parsed them
        by_shard: dict[str, list[str]] = {}
        for r, _d, files in os.walk(trace_dir):
            for f in files:
                by_shard.setdefault(f.split(".")[0], []).append(
                    os.path.join(r, f))
        batcher = _Batcher(matcher, dest_dir, report_levels,
                           transition_levels, quantisation, source, mode,
                           threshold_sec)
        pending: list = []
        for _shard, paths in sorted(by_shard.items()):
            with metrics.timer("pipeline.parse"):
                pending += _shard_windows(paths, inactivity, threshold_sec,
                                          batcher.options)
            while len(pending) >= device_batch:
                batcher.run(pending[:device_batch])
                del pending[:device_batch]
        if pending:
            batcher.run(pending)
        logger.info("Matched %d windows total", batcher.windows)
    return dest_dir


class _Batcher:
    """One stage-2 call's device batches: match, report, seam filter and
    tile rows, batch by batch.

    Exactly-once across chunk seams: a uuid's windows reach ``run`` in
    time order, possibly over two calls, and pair start times are
    strictly increasing along a trace, so dropping reports at or below
    the uuid's highest-emitted t0 (one floor per uuid for the whole
    stage) removes seam duplicates and nothing else."""

    def __init__(self, matcher, dest_dir: str, report_levels: set,
                 transition_levels: set, quantisation: int, source: str,
                 mode: str, threshold_sec: int):
        self.matcher = matcher
        self.dest_dir = dest_dir
        self.levels = (report_levels, transition_levels)
        self.quantisation = quantisation
        self.source = source
        self.mode = mode
        self.threshold_sec = threshold_sec
        self.options = {"mode": mode}
        self.last_t0: dict[str, float] = {}
        self.windows = 0

    def run(self, parts: list) -> None:
        from ..core.tracebatch import TraceBatch
        from ..service.report import report as make_report

        tb = TraceBatch.concat(parts)
        self.windows += len(parts)
        metrics.count("pipeline.batches")
        metrics.count("pipeline.windows", len(parts))
        metrics.count("pipeline.points", int(tb.offsets[-1]))
        try:
            with metrics.timer("pipeline.match_many"):
                matches = self.matcher.match_many(tb)
        except Exception:
            metrics.count("pipeline.batch_failures")
            logger.exception("Batch match failed for %d windows",
                             len(parts))
            return
        with metrics.timer("pipeline.rows"):
            tiles: dict[str, list[str]] = {}
            dropped = 0
            for trace, match in zip(tb, matches):
                uuid = trace["uuid"]
                try:
                    rep = make_report(match, trace, self.threshold_sec,
                                      *self.levels)
                except Exception:
                    metrics.count("pipeline.report_failures")
                    logger.exception("Failed to report a window of uuid %s",
                                     uuid)
                    continue
                floor = self.last_t0.get(uuid)
                reports = rep["datastore"]["reports"]
                if floor is not None:
                    kept = [r for r in reports if r["t0"] > floor]
                    dropped += len(reports) - len(kept)
                    reports = rep["datastore"]["reports"] = kept
                if reports:
                    self.last_t0[uuid] = max(r["t0"] for r in reports)
                _emit_rows(rep, trace, self.quantisation, self.source,
                           self.mode, tiles)
            rows = 0
            for tile_file, lines in tiles.items():
                path = os.path.join(self.dest_dir, tile_file)
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "a") as f:
                    f.writelines(lines)
                rows += len(lines)
            metrics.count("pipeline.seam_dropped", dropped)
            metrics.count("pipeline.rows", rows)


def _emit_rows(rep: dict, trace: dict, quantisation: int, source: str,
               mode: str, tiles: dict) -> None:
    """Usable reports -> per-bucket tile rows
    (reference: simple_reporter.py:176-196)."""
    points = trace["trace"]
    max_buckets = (points[-1]["time"] - points[0]["time"]) // quantisation + 1
    for r in rep["datastore"]["reports"]:
        if not (r["t0"] > 0 and r["t1"] > 0 and r["t1"] - r["t0"] > 0.5
                and r["length"] > 0 and r["queue_length"] >= 0):
            continue
        duration = int(round(r["t1"] - r["t0"]))
        start = int(math.floor(r["t0"]))
        end = int(math.ceil(r["t1"]))
        lo_b, hi_b = start // quantisation, end // quantisation
        if hi_b - lo_b > max_buckets:
            logger.error("Segment spans %d buckets but should be <= %d",
                         hi_b - lo_b, max_buckets)
            continue
        for b in range(lo_b, hi_b + 1):
            tile_file = os.path.join(
                f"{b * quantisation}_{(b + 1) * quantisation - 1}",
                str(tile_level(r["id"])), str(tile_index(r["id"])))
            row = ",".join([
                str(r["id"]), str(r.get("next_id", INVALID_SEGMENT_ID)),
                str(duration), "1", str(r["length"]),
                str(r["queue_length"]), str(start), str(end),
                source, mode.upper()]) + "\n"
            tiles.setdefault(tile_file, []).append(row)


# --------------------------------------------------------------------------
# stage 3: report
# --------------------------------------------------------------------------

def _report_worker(files: List[str], match_dir: str, dest: str,
                   privacy: int, results) -> None:
    """Anonymise ``files`` in turn, sending each file's tally, ``(tiles
    written, rows kept)``, or None where the file raised, down the
    ``results`` pipe."""
    for path in files:
        try:
            rows = _report_file(path, match_dir, dest, privacy)
            tally = (1 if rows else 0, rows)
        except Exception as e:
            tally = None
            logger.error("%s was not reported: %s", path, e)
        results.send(tally)


def _report_file(path: str, match_dir: str, dest: str, privacy: int) -> int:
    """One tile file of ``match_dir``, sorted and privacy-culled, to
    ``dest``; returns the rows kept (0: nothing written)."""
    with open(path) as f:
        rows = f.readlines()
    rows.sort()
    # cull rows below the privacy threshold on (segment, next) runs
    kept: list[str] = []
    i = 0
    while i < len(rows):
        ki = rows[i].split(",")[:2]
        j = i
        while j < len(rows) and rows[j].split(",")[:2] == ki:
            j += 1
        if j - i >= privacy:
            kept.extend(rows[i:j])
        i = j
    rel = os.path.relpath(path, match_dir)
    if not kept:
        logger.info("No segments for %s after anonymising", rel)
        return 0
    name = hashlib.sha1(path.encode()).hexdigest()
    payload = Segment.column_layout() + "\n" + "".join(kept)
    key = rel + "/" + name
    logger.info("Writing %d segments to %s", len(kept), key)
    if _is_remote(dest):
        if not _put_remote(dest, key, payload):
            raise RuntimeError(f"{dest} did not take {key}")
    else:
        out_path = os.path.join(dest, key)
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            f.write(payload)
    return len(kept)


def _is_remote(dest: str) -> bool:
    return dest.startswith(("s3://", "http://", "https://"))


def _put_remote(dest: str, key: str, payload: str) -> bool:
    """One tile to a remote sink; returns whether the sink took it."""
    if not dest.startswith("s3://"):
        # signed PUT / boto3 for AWS endpoints, plain POST otherwise —
        # same routing as the streaming TileSink
        from ..utils import http as http_egress
        return http_egress.egress_tile(dest, key, payload)
    try:
        import boto3
    except ImportError:
        logger.error("s3 destination requires boto3, which is unavailable")
        return False
    bucket, _, prefix = dest[len("s3://"):].partition("/")
    full_key = (prefix.rstrip("/") + "/" + key) if prefix else key
    boto3.client("s3").put_object(Bucket=bucket, Key=full_key,
                                  Body=payload.encode())
    return True


def report_tiles(match_dir: str, dest: str, privacy: int,
                 concurrency: int) -> None:
    """Stage 3 driver: every tile file of ``match_dir``, anonymised, to
    ``dest`` (a local directory or a remote sink).

    Counts ``pipeline.report.tiles`` and ``pipeline.report.rows``, the
    tiles written and the rows they kept, and ``pipeline.tile_failures``
    for each tile file that raised or whose worker died before reporting
    it."""
    with metrics.timer("pipeline.report"):
        files = sorted(
            os.path.join(r, f)
            for r, _d, fs in os.walk(match_dir) for f in fs)
        logger.info("Reporting %d anonymised time tiles", len(files))
        if not _is_remote(dest):
            os.makedirs(dest, exist_ok=True)
        chunks = [files[i::concurrency] for i in range(concurrency)]
        tallies = _run_workers(_report_worker, chunks, match_dir, dest,
                               privacy)
        done = [t for t in tallies if t is not None]
        tiles, rows = [sum(col) for col in zip(*done)] or [0, 0]
        metrics.count("pipeline.report.tiles", tiles)
        metrics.count("pipeline.report.rows", rows)
        metrics.count("pipeline.tile_failures", len(files) - len(done))


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _bbox(arg: str) -> List[float]:
    b = [float(x) for x in arg.split(",")]
    if b[0] < -90 or b[1] < -180 or b[2] > 90 or b[3] > 180 \
            or b[0] >= b[2] or b[1] >= b[3]:
        raise argparse.ArgumentTypeError(f"{arg} is not a valid bbox")
    return b


def _int_set(arg: str) -> set:
    return {int(x) for x in arg.split(",")}


DEFAULT_VALUER = ("lambda l: functools.partial(lambda c: "
                  "[c[1], c[0], c[9], c[10], c[5]], l.split('|'))()")


def main(argv=None):
    parser = argparse.ArgumentParser(prog="simple-reporter")
    parser.add_argument("--src", help="local dir of part files or s3://bucket/prefix")
    parser.add_argument("--src-key-regex", default=".*")
    parser.add_argument("--src-valuer", default=DEFAULT_VALUER,
                        help="lambda extracting (uuid, time, lat, lon, accuracy)")
    parser.add_argument("--src-time-pattern", default="%Y-%m-%d %H:%M:%S")
    parser.add_argument("--match-config", required=True,
                        help="matcher config json (graph path + knobs)")
    parser.add_argument("--mode", default="auto")
    parser.add_argument("--report-levels", type=_int_set, default={0, 1})
    parser.add_argument("--transition-levels", type=_int_set, default={0, 1})
    parser.add_argument("--quantisation", type=int, default=3600)
    parser.add_argument("--inactivity", type=int, default=120)
    parser.add_argument("--privacy", type=int, default=2)
    parser.add_argument("--source-id", default="smpl_rprt")
    parser.add_argument("--dest", help="output dir or s3://bucket[/prefix]")
    parser.add_argument("--concurrency", type=int,
                        default=multiprocessing.cpu_count())
    parser.add_argument("--bbox", type=_bbox,
                        default=[-90.0, -180.0, 90.0, 180.0])
    parser.add_argument(
        "--trace-dir",
        help="resume: a trace dir this build's stage 1 gathered (shard "
             "files of columnar blocks: a 32-byte header tagged RTSHARD, "
             "a uuid table, 32-byte records of epoch, lat, lon, accuracy "
             "and uuid code; see the module docstring); a CSV trace dir "
             "of an earlier build is refused and must be gathered again")
    parser.add_argument("--match-dir", help="resume: pre-matched segments")
    parser.add_argument("--device-batch", type=int, default=512)
    parser.add_argument("--cleanup", action=argparse.BooleanOptionalAction,
                        default=True)
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")

    from ..matcher import Configure, SegmentMatcher

    trace_dir = args.trace_dir
    match_dir = args.match_dir
    if not trace_dir and not match_dir:
        if not args.src:
            parser.error("--src is required unless resuming")
        trace_dir = gather_traces(args.src, args.src_key_regex,
                                  args.src_valuer, args.src_time_pattern,
                                  args.bbox, args.concurrency)
    if not match_dir:
        # joins a multi-host JAX job when a coordinator is configured;
        # single-host no-op otherwise. Deliberately AFTER the gather stage
        # (which needs no devices) so the coordinator rendezvous doesn't
        # gate pure-IO work; worker processes are never forked from this
        # one (_MP above), so jax.distributed's threads are never
        # inherited mid-state either.
        from ..parallel import init_multihost
        from ..utils.runtime import ensure_backend
        ensure_backend()
        init_multihost()
        Configure(args.match_config)
        matcher = SegmentMatcher()
        match_dir = match_traces(
            trace_dir, matcher, args.mode, args.report_levels,
            args.transition_levels, args.quantisation, args.inactivity,
            args.source_id, device_batch=args.device_batch)
    if args.dest:
        report_tiles(match_dir, args.dest, args.privacy, args.concurrency)
    timers = metrics.snapshot()["timers"]
    logging.info("Stage timings: %s", {
        k: v["total_s"] for k, v in timers.items()
        if k.startswith("pipeline.")})
    if args.cleanup:
        for d in (trace_dir, match_dir):
            if d and not (d == args.trace_dir or d == args.match_dir):
                shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
