"""Stream backlog: one streaming worker drains a partition's backlog of
1 Hz probes into anonymised tiles (``reporter_tpu stream`` with
in-process matching, ``--punctuate stream``), as after a restart or an
outage.

Traffic parameters: the stream's (``stream_gen.py``: ``vehicles``,
``sample_period_s``, ``trip_s``, ``start_s``), the worker's
(``report_flush_interval_s``, ``punctuate``, ``report_levels``,
``transition_levels``) and ``warm_points``, the longest window whose
decode programs the warm-up builds. The configuration's ``stream`` group
gives the privacy, the quantisation and the tile flush interval.
End-to-end metric: ``traces_per_s``, the report windows the plain
reference forms on the stream (``ref/stream.py``; a window due again
with nothing consumed counts again) over the job's seconds, from the
first offer to the drain's last tile written. The numerator is the
data's, not the program's: a window the program submits beyond the
reference's is work, not throughput (its own count, ``stream.windows``,
is in the check's numbers). The backlog is offered as fast as the
worker takes it; the job runs to its end, and one still running after
four times ``--seconds`` fails the run.

Set-up builds the graph and the stream from the seed (the messages held
in memory), makes the program's ``SegmentMatcher`` from the
configuration, warms every decode program a declined or evicted window
can form (``SegmentMatcher.warm``) and the carried-state advance at
every shape a flush of the fleet can pad to
(``SegmentMatcher.warm_incremental``). The window builds the worker
through the program's own in-process wiring (``inprocess_worker``) with
the program's ``Anonymiser`` and ``TileSink`` writing into a scratch
directory under the cache, and runs ``StreamWorker.run`` over the
messages in a thread.

The run fails where the worker lost a window or the carried-state
advance failed (``LOST``), besides the harness's own rules. The check
(limits in the configuration's ``check.limits``):

- ``segment_mismatch``: the larger of two shares.
  ``row_share``: over the whole fleet (the privacy cull couples
  vehicles, so a sample cannot be checked alone), the plain reference's
  rows (``ref/stream.py``) that the program lacks plus the rows it holds
  beyond them, over the reference's rows, for two sets of rows at once:
  the rows each flush would write before the cull (the program's as
  its anonymiser is handed them, ``ObservationSpy``), and the rows the
  tile files keep. At this fleet's density the cull keeps few rows,
  so the rows before it carry the comparison of windows, reports and
  flush epochs, and the files that of the cull. A row is keyed by its
  tile directory, its file (the flush epoch) and its ids, lengths and
  text; its three times are those of a report whose ``t0`` and ``t1``
  lie within ``TIME_TOL_S`` of the reference's.
  ``window_share``: the report levels keep few of a window's segments,
  so every window of ``CHECK_VEHICLES`` vehicles drawn from the seed
  (``WindowSpy``: the request the worker submitted and the answer it
  got, carried state or not) is held to ``ref.pipeline``'s decode of
  that whole window, as the served cells' answers are (``check.py``:
  each matched segment and report, and the window's ``shape_used``):
  the share of the reference's items the answers lack;
- ``unanswered``: report windows the worker did not have answered at
  once (``batch.requeued``, ``batch.dropped``, ``backpressure.shed``).
"""
from __future__ import annotations

import inspect
import json
import math
import multiprocessing
import os
import random
import shutil
import threading
import time

import gen
import stream_gen
from check import TIME_TOL_S, compare
from harness import CACHE, Failure, Window, check_zero, delta, log, snapshot

#: worker processes of the check (fewer in a rehearsal, on the CPU
#: beside the tests)
PROCS = 12
REHEARSE_PROCS = 2
#: the program's counters of lost work: any move fails the run
LOST = ("batch.dropped", "batch.deadletter", "backpressure.shed",
        "match.incremental.errors")
#: counters of windows not answered at the first submit
UNANSWERED = ("batch.requeued", "batch.dropped", "backpressure.shed")
#: vehicles whose every window's answer is held to the whole-window
#: decode
CHECK_VEHICLES = 32
SOURCE = "rtpu_stream"
MODE = "auto"


def check_lost(counters: dict) -> None:
    """Fail a run in which the worker lost a window."""
    bad = {k: counters[k] for k in LOST if counters.get(k)}
    if bad:
        raise Failure(f"the worker lost work: {bad}")


class ObservationSpy:
    """Wraps the worker's anonymiser's ``process`` for the window: keeps
    the rows each observation it is handed adds to its tiles, as (tile
    dir, the file the next flush writes, row), then hands it on to the
    real ``process``."""

    def __init__(self, anonymiser):
        from reporter_tpu.core.types import TimeQuantisedTile
        self.anonymiser = anonymiser
        self.real = anonymiser.process
        self.tiles_for = TimeQuantisedTile.tiles_for
        self.rows = []
        anonymiser.process = self._process

    def _process(self, key, segment):
        a = self.anonymiser
        q = a.quantisation
        name = a.epoch_file_name(a.flush_epoch)
        row = segment.csv_row(a.mode, a.source)
        self.rows += [(f"{t.time_range_start}_{t.time_range_start + q - 1}"
                       f"/{t.tile_level()}/{t.tile_index()}", name, row)
                      for t in self.tiles_for(segment, q)]
        return self.real(key, segment)


class WindowSpy:
    """Wraps the worker's batched submit for the window: keeps each
    window of the vehicles in ``uuids`` as the ``/report`` request it
    was submitted as, with the answer the program gave it."""

    def __init__(self, batcher, uuids):
        self.batcher = batcher
        self.real = batcher.submit_many
        self.uuids = frozenset(uuids)
        self.windows = []
        batcher.submit_many = self._submit

    def _submit(self, tb):
        out = self.real(tb)
        self.windows += [(tb, i, out[i]) for i, u in enumerate(tb.uuids)
                         if u in self.uuids]
        return out

    def pairs(self) -> list:
        """(request, answer as JSON carries it) of each kept window."""
        return [(_plain(tb[i].to_request()), _plain(_cut(body)))
                for tb, i, body in self.windows]


def _cut(body: "dict | None") -> "dict | None":
    """What the comparison reads of an answer."""
    if body is None:
        return None
    out = {"segment_matcher": {"segments": list(
        body["segment_matcher"].get("segments") or [])},
        "datastore": {"reports": body["datastore"]["reports"]}}
    if "shape_used" in body:
        out["shape_used"] = body["shape_used"]
    return out


def _plain(obj):
    return json.loads(json.dumps(obj, default=lambda o: o.tolist()))


def window_mismatch(have: list, want: list) -> tuple:
    """(items of the reference's answers that the program's answers lack,
    items in the reference's answers), over windows in order: each
    matched segment and report as ``check.compare`` keys it, and the
    window's ``shape_used`` as one item."""
    missing = total = 0
    for served, expected in zip(have, want):
        m, t, _differ = compare(served, expected)
        missing += m + (served is None or served.get("shape_used")
                        != expected.get("shape_used"))
        total += t + 1
    return missing, total


def read_tiles(dest: str) -> list:
    """(tile dir, file name, row) of every row of the tile files under
    ``dest`` (the sink's dot-named spools left out)."""
    out = []
    for root, dirs, files in os.walk(dest):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        tile_dir = os.path.relpath(root, dest)
        for name in files:
            with open(os.path.join(root, name)) as f:
                out += [(tile_dir, name, row)
                        for row in f.read().split("\n")[1:] if row]
    return out


def _key(tile_dir: str, name: str, row: str) -> tuple:
    """(the row's file and text but its three times; its duration,
    first and last second)."""
    c = row.split(",")
    return ((tile_dir, name, c[0], c[1], c[3], c[4], c[5], c[8], c[9]),
            (int(c[2]), int(c[6]), int(c[7])))


def _times_hold(times: tuple, t0: float, t1: float) -> bool:
    """Whether a row's duration, first and last second are written from
    some report times within ``TIME_TOL_S`` of (t0, t1)."""
    dur, lo, hi = times
    tol = TIME_TOL_S
    return (math.floor(t0 - tol) <= lo <= math.floor(t0 + tol)
            and math.ceil(t1 - tol) <= hi <= math.ceil(t1 + tol)
            and math.floor(t1 - t0 - 2 * tol + 0.5) <= dur
            <= math.floor(t1 - t0 + 2 * tol + 0.5))


def row_mismatch(have: list, want: list) -> tuple:
    """(rows of ``want`` (tile dir, file, row, t0, t1) that ``have``
    (tile dir, file, row) lacks plus rows of ``have`` beyond them, rows
    in ``want``)."""
    pool = {}
    for key, times in (_key(*r) for r in have):
        pool.setdefault(key, []).append(times)
    missing = 0
    for tile_dir, name, row, t0, t1 in want:
        key, _times = _key(tile_dir, name, row)
        got = pool.get(key, [])
        hit = next((k for k, t in enumerate(got)
                    if _times_hold(t, t0, t1)), None)
        if hit is None:
            missing += 1
        else:
            got.pop(hit)
    return missing + sum(len(v) for v in pool.values()), len(want)


# ------------------------------------------------------- check processes

_refs = None


def init_check(config: dict, seed: int, control: bool) -> None:
    """A check worker's graph and plain reference (``ref.pipeline``; with
    ``control``, the reference one step down in precision as well)."""
    global _refs
    from ref.control import viterbi_decode_bf16
    from ref.pipeline import Reference
    net = gen.build_graph(config, seed)
    thr = config["service"]["threshold_sec"]
    _refs = {"reference": Reference(net, config["matcher"], thr)}
    if control:
        _refs["control"] = Reference(net, config["matcher"], thr,
                                     decode=viterbi_decode_bf16, wire="fp8")


def answer(job: tuple) -> dict:
    """One window's answer by the reference named ``job[0]``, cut to
    what the stream reference reads."""
    which, request = job
    body = _refs[which].report(request)
    out = {"segment_matcher": {"segments": [1] if body[
        "segment_matcher"].get("segments") else []},
        "datastore": {"reports": body["datastore"]["reports"]}}
    if "shape_used" in body:
        out["shape_used"] = body["shape_used"]
    return out


def whole(job: tuple) -> dict:
    """One window's whole answer by the reference named ``job[0]``."""
    which, request = job
    return _cut(_refs[which].report(request))


def expected_rows(pool, lines: list, config: dict, traffic: dict,
                  which: str) -> tuple:
    """(the rows before the cull, the tile files' rows, the report
    windows) of the stream reference over ``lines``, answered by the
    reference named ``which``."""
    from ref.stream import StreamReference
    s = config["stream"]
    ref = StreamReference(
        lambda reqs: pool.map(answer, [(which, r) for r in reqs]),
        s["privacy"], s["quantisation_s"], s["flush_interval_s"],
        traffic["report_flush_interval_s"], MODE, SOURCE,
        traffic["report_levels"], traffic["transition_levels"])
    return (*ref.run(lines), ref.windows)


# ---------------------------------------------------------------- the run

def serve(cell, args, clock, t_start: float, trace_dir) -> dict:
    scratch = os.path.join(CACHE, f"stream-{os.getpid()}")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    try:
        return _serve(cell, args, clock, t_start, trace_dir, scratch)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def _serve(cell, args, clock, t_start, trace_dir, scratch) -> dict:
    from reporter_tpu import native
    from reporter_tpu.matcher import Configure, SegmentMatcher
    from reporter_tpu.streaming import worker as stream_worker
    from reporter_tpu.streaming.anonymiser import Anonymiser, TileSink
    from reporter_tpu.streaming.formatter import Formatter

    if not hasattr(stream_worker, "inprocess_worker") or "punctuate" \
            not in inspect.signature(stream_worker.StreamWorker).parameters:
        raise Failure("the program's streaming worker has no stream-time "
                      "punctuation (StreamWorker(punctuate=)) or no "
                      "in-process wiring (inprocess_worker): it cannot "
                      "replay a backlog as the live stream ran")
    if not hasattr(SegmentMatcher, "warm_incremental"):
        raise Failure("the program's SegmentMatcher has no "
                      "warm_incremental(): it cannot build the carried-"
                      "state step before the window")
    conf, traffic = cell.config, cell.traffic
    s = conf["stream"]
    t0 = time.perf_counter()
    net = gen.build_graph(conf, args.seed)
    graph = os.path.join(scratch, "graph.npz")
    net.save(graph)
    lines, made = stream_gen.backlog(net, conf, traffic, args.seed)
    log("stream", stream_s=time.perf_counter() - t0, **made)

    Configure({"graph": graph, "matcher": conf["matcher"]})
    matcher = SegmentMatcher()
    if not native.available():
        raise Failure("native host runtime did not load")
    opts = {"mode": MODE, "report_levels": traffic["report_levels"],
            "transition_levels": traffic["transition_levels"]}
    warm = [{"uuid": "warm", "trace": t, "match_options": opts}
            for t in stream_gen.warm_trips(net, conf, traffic, args.seed,
                                           16, traffic["warm_points"])]
    start = snapshot()
    t1 = time.perf_counter()
    n_warm = matcher.warm(warm, traffic["warm_points"])
    n_steps = matcher.warm_incremental(traffic["vehicles"])
    warm_s = time.perf_counter() - t1
    log("warm", warm_shapes=n_warm, warm_steps=n_steps, warm_s=warm_s)
    check_zero("warm-up", delta(start, snapshot())[0])

    dest = os.path.join(scratch, "tiles")
    anonymiser = Anonymiser(TileSink(dest), s["privacy"],
                            s["quantisation_s"], mode=MODE, source=SOURCE)
    spy = ObservationSpy(anonymiser)
    worker = stream_worker.inprocess_worker(
        matcher, Formatter.from_config(stream_gen.FORMATTER), anonymiser,
        mode=MODE,
        reports=",".join(str(x) for x in traffic["report_levels"]),
        transitions=",".join(str(x) for x in traffic["transition_levels"]),
        flush_interval_s=s["flush_interval_s"],
        report_flush_interval_s=traffic["report_flush_interval_s"],
        punctuate=traffic["punctuate"])
    fleet = traffic["vehicles"]
    sampled = WindowSpy(worker.batcher, [
        stream_gen.uuid_of(args.seed, i) for i in random.Random(
            args.seed).sample(range(fleet), min(fleet, CHECK_VEHICLES))])
    window = Window(clock, trace_dir, args.fault)
    out = {}

    def job() -> None:
        try:
            worker.run(lines)
            out["done"] = time.perf_counter()
        except BaseException as e:
            out["error"] = e

    with window:
        t_job = time.perf_counter()
        th = threading.Thread(target=job, daemon=True)
        th.start()
        th.join(timeout=4 * args.seconds)
    if th.is_alive():
        raise Failure(f"the job did not end within {4 * args.seconds} s")
    if "error" in out:
        raise Failure(f"the job failed: {out['error']!r}")
    job_s = out["done"] - t_job
    window.finish(start, job_s=job_s, **made)
    c = window.counters
    log("worker", **{k: c.get(k, 0) for k in (
        "stream.points", "stream.windows", "match.incremental.matches",
        "match.incremental.fallbacks", "match.incremental.steps",
        "backpressure.delays", "egress.ok")})
    check_lost(c)

    t2 = time.perf_counter()
    have = (spy.rows, read_tiles(dest))
    requests, answers = zip(*sampled.pairs()) if sampled.windows \
        else ((), ())
    procs = REHEARSE_PROCS if cell.rehearse else PROCS
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(procs, init_check,
                  (conf, args.seed, bool(args.control))) as pool:
        *want, ref_windows = expected_rows(pool, lines, conf, traffic,
                                           "reference")
        wholes = pool.map(whole, [("reference", r) for r in requests])
        if args.control:
            *ctl, _n = expected_rows(pool, lines, conf, traffic, "control")
            have = tuple([r[:3] for r in rows] for rows in ctl)
            answers = pool.map(whole, [("control", r) for r in requests])
    (m_obs, n_obs), (m_tiles, n_tiles) = (
        row_mismatch(h, w) for h, w in zip(have, want))
    m_win, n_win = window_mismatch(answers, wholes)
    windows = c.get("stream.windows", 0)
    unanswered = sum(c.get(k, 0) for k in UNANSWERED)
    n_rows = n_obs + n_tiles
    row_share = (m_obs + m_tiles) / n_rows if n_rows else 1.0
    window_share = m_win / n_win if n_win else 1.0
    check = {"segment_mismatch": max(row_share, window_share),
             "unanswered": unanswered, "row_share": row_share,
             "observed_mismatch": m_obs, "observed_rows": n_obs,
             "tile_mismatch": m_tiles, "tile_rows": n_tiles,
             "window_share": window_share, "checked_windows": len(answers),
             "window_items": n_win, "reference_windows": ref_windows,
             "windows": windows, "check_s": time.perf_counter() - t2}
    return window.result(
        t_start,
        end_to_end={"traces_per_s": {"value": ref_windows / job_s,
                                     "unit": "traces/s"}},
        check=check, attempted=windows + unanswered, failed=unanswered)
