"""The streaming worker: raw probe stream -> anonymised traffic tiles.

Single-process topology mirroring the reference's Kafka Streams worker
(reference: Reporter.java:21-39 topology diagram, :138-194 wiring):

    raw -> [Formatter] -> (uuid, Point) -> [PointBatcher] -> (key, Segment)
        -> [Anonymiser] -> tiles -> file / http / s3

with the matcher reached either in-process (default — micro-batched onto
the TPU via the service dispatcher) or over HTTP for split deployments
(the reference's only mode, Batch.java:66-72).

CLI options named after the reference's (Reporter.java:43-136):
  --formatter/-f   one-string formatter config
  --reporter-url/-u  http endpoint; omit for in-process matching
  --mode/-m --reports/-r --transitions/-x
  --privacy/-p --quantisation/-q --flush-interval/-i
  --source/-s --output-location/-o --duration/-d
plus --input (flat file / '-' for stdin replay) or --bootstrap/-b with
--topics/-t for Kafka, and --punctuate: ``wall`` (the default) paces the
report flush, the session-gap eviction and the tile flush by the wall
clock; ``stream`` paces them by stream time, the largest probe time
offered so far, as the reference's Kafka Streams processors are
punctuated (BatchingProcessor.java:87-106,
AnonymisingProcessor.java:223-266), so a backlog replayed at any speed
forms the same report windows and tile flushes as the live stream did.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import sys
import time
from typing import Callable, Iterable, Optional

from ..obs import flightrec, profiler
from ..obs import trace as obs_trace
from ..utils import faults, metrics
from ..utils import http as http_egress
from ..utils import spool as spool_mod
from .anonymiser import Anonymiser, TileSink
from .batcher import PointBatcher, SESSION_GAP_MS
from .drainer import DeadLetterDrainer, replay_knobs
from .formatter import Formatter

logger = logging.getLogger("reporter_tpu.streaming")


def _pressure_level() -> dict:
    """The process-wide degradation-ladder state for the heartbeat
    (lazy import: a pure-streaming process without the serving tier
    loaded reports the quiescent shape without importing it)."""
    import sys
    admission = sys.modules.get("reporter_tpu.service.admission")
    if admission is None:
        return {"level": 0, "state": "normal", "transitions": 0}
    return admission.pressure_snapshot()


def http_submitter(url: str) -> Callable[[dict], Optional[dict]]:
    """POST the trace to a matcher service, with the reference's retry
    policy; returns parsed JSON or None (reference: HttpClient.java:65-103).
    """
    def submit(trace: dict) -> Optional[dict]:
        body = json.dumps(trace, separators=(",", ":"))
        text = http_egress.post(url, body, content_type="application/json")
        if text is None:
            return None
        try:
            return json.loads(text)
        except json.JSONDecodeError as e:
            logger.error("unparseable matcher response from %s: %s", url, e)
            return None
    return submit


def inproc_submitter(service) -> Callable[[dict], Optional[dict]]:
    """Use a ReporterService in this process — the TPU-native default."""
    def submit(trace: dict) -> Optional[dict]:
        code, body = service.handle(trace)
        if code != 200:
            logger.error("in-process match failed (%d): %s", code, body)
            return None
        # the native wire path hands back a memoryview of the chunk
        # buffer (zero-copy for sockets); json.loads wants bytes/str
        return json.loads(bytes(body) if isinstance(body, memoryview)
                          else body)
    return submit


#: punctuation clocks: the wall clock, or stream time (the largest probe
#: time offered so far)
PUNCTUATE = ("wall", "stream")


class StreamWorker:
    """Wires formatter -> batcher -> anonymiser and drives punctuation."""

    def __init__(self, formatter: Formatter,
                 submit: Callable[[dict], Optional[dict]],
                 anonymiser: Anonymiser,
                 mode: str = "auto", reports: str = "0,1",
                 transitions: str = "0,1",
                 flush_interval_s: float = 3600.0,
                 session_gap_ms: int = SESSION_GAP_MS,
                 clock=time.time,
                 state=None,
                 uuid_filter: Optional[Callable[[str], bool]] = None,
                 submit_many=None,
                 report_flush_interval_s: float = 1.0,
                 trace_deadletter: Optional[str] = None,
                 circuit_probe: Optional[Callable[[], str]] = None,
                 degraded_probe: Optional[Callable[[], list]] = None,
                 incremental_probe: Optional[
                     Callable[[], Optional[dict]]] = None,
                 on_evict: Optional[Callable[[str], None]] = None,
                 datastore=None, compactor=None,
                 map_version: Optional[str] = None,
                 punctuate: str = "wall"):
        if punctuate not in PUNCTUATE:
            raise ValueError(f"punctuate must be one of {PUNCTUATE}")
        self.formatter = formatter
        # content-derived identity of the graph this worker matches
        # against (graph/version.py), surfaced in the heartbeat so a
        # fleet's log pipeline can see which map build each worker is
        # on across a swap; None when the owner wired no graph probe
        self.map_version = map_version
        # multi-host: predicate deciding which uuids this worker owns
        # (parallel.multihost — the Kafka keyed-partition contract when the
        # input stream is not already partitioned); None = own everything
        self.uuid_filter = uuid_filter
        self.skipped_other_host = 0
        self.anonymiser = anonymiser
        spool = getattr(getattr(anonymiser, "sink", None),
                        "deadletter", None)
        if trace_deadletter is None:
            # default next to the tile dead-letter spool, dot-prefixed so
            # `datastore ingest` over that spool never mistakes a trace
            # JSON for a tile CSV (ingest.scan_tiles skips it by name);
            # stub sinks without a spool leave it off (log-and-drop)
            if spool:
                trace_deadletter = os.path.join(spool, ".traces")
        # the flight recorder dumps its postmortems next to the spools
        # (same layout contract: dot-prefixed, skipped by scan_tiles);
        # an explicit REPORTER_TPU_FLIGHTREC wins inside set_dump_dir
        if spool:
            flightrec.set_dump_dir(os.path.join(spool, ".flightrec"))
        # register the spool roots so the matcher's poisoned-trace
        # quarantine and /health's backlog gauges find them without
        # plumbing (utils.spool, module-level like the flight recorder).
        # Last-writer-wins: a multi-worker process (tools/bigreplay.py)
        # must wire matcher.quarantine_spool per matcher instead of
        # relying on these globals, and the heartbeat below reads the
        # per-instance roots, not the globals
        self._tile_spool = spool or None
        self._trace_spool = trace_deadletter
        spool_mod.set_tile_dir(spool or None)
        spool_mod.set_trace_dir(trace_deadletter)
        # automated dead-letter replay (ISSUE 9): paced on THIS thread
        # via maybe_punctuate (the anonymiser/batcher have no locks),
        # re-submitting spooled traces through the live submit path and
        # re-egressing spooled tiles through the live sink.
        # REPORTER_TPU_REPLAY_INTERVAL_S=0 (default) disables.
        replay_interval, replay_attempts = replay_knobs()
        self.drainer = None
        if replay_interval > 0 and (spool or trace_deadletter):
            self.drainer = DeadLetterDrainer(
                spool or None, trace_root=trace_deadletter,
                submit=submit,
                forward=lambda key, seg: self.anonymiser.process(key, seg),
                sink=getattr(anonymiser, "sink", None),
                datastore=datastore,
                interval_s=replay_interval,
                max_attempts=replay_attempts)
        self.batcher = PointBatcher(
            submit, lambda key, seg: self.anonymiser.process(key, seg),
            mode=mode, report_on=reports, transition_on=transitions,
            session_gap_ms=session_gap_ms, submit_many=submit_many,
            deadletter_dir=trace_deadletter, on_evict=on_evict)
        self.flush_interval_s = flush_interval_s
        self.session_gap_ms = session_gap_ms
        # stream-time punctuation: ``now`` is the largest probe time
        # offered so far (None before the first), and the first probe
        # starts every punctuation schedule (:meth:`_advance`). An
        # injected ``clock`` paces wall punctuation only; ``run``'s
        # ``duration_s`` is read on it in either mode
        if punctuate == "stream" and clock is not time.time:
            raise ValueError("an injected clock paces wall punctuation; "
                             "stream punctuation reads probe times")
        self.punctuate = punctuate
        self._stream_now: Optional[float] = None
        self._wall_clock = clock
        if punctuate == "stream":
            clock = self._stream_clock
        self.clock = clock
        self.processed = 0
        self.parse_failures = 0
        self._last_flush = clock()
        self._last_evict = clock()
        # wall-clock bound on how long a threshold-crossed session may sit
        # in the batcher's pending set before a batched flush: keeps live
        # report latency near the reference's immediate-fire behavior
        # while a fast replay still accumulates whole device batches
        self.report_flush_interval_s = report_flush_interval_s
        self._last_report_flush = clock()
        # structured heartbeat: the reference logged a bare counter every
        # 10k messages (KeyedFormattingProcessor.java:36-38); this one is
        # wall-clock paced (monotonic — independent of injected replay
        # clocks) and single-line JSON so a log pipeline can chart it.
        # 0 disables.
        from ..utils.runtime import _env_float
        self.heartbeat_s = _env_float("REPORTER_TPU_HEARTBEAT_S", 0.0)
        # circuit-state probe for the heartbeat (in-process deployments
        # pass the matcher's breaker; HTTP splits have none to read);
        # degraded_probe names the OPEN domains (matcher.open_domains)
        self.circuit_probe = circuit_probe
        self.degraded_probe = degraded_probe
        # carried-state gauge for the incremental matcher path
        # (IncrementalTable.gauge(); None until the table exists / on
        # HTTP split deployments, which have no in-process matcher)
        self.incremental_probe = incremental_probe
        self._hb_last = time.monotonic()
        self._hb_processed = 0
        # background compaction (datastore/compactor.py): the delta-
        # pressure policy off the flush hot path — the tee ingest no
        # longer compacts inline; the paced thread (lease-gated) does.
        # Owned here so drain() can stop it in dependency order.
        self.compactor = compactor
        self.datastore = datastore
        if compactor is not None:
            compactor.start()
        # durable state (StateStore): restore open batches + tile slices
        # from the last snapshot — the reference instead loses in-memory
        # state on crash (BatchingProcessor.java:20-22, SURVEY.md §5)
        self.state = state
        self.restored = bool(
            state and state.restore(self.batcher, self.anonymiser))

    def offer(self, message: str) -> None:
        """One raw message through the topology."""
        # the per-message span (no-op flag check unless tracing is
        # armed) opens BEFORE the crash failpoint so a SIGKILL-grade
        # death lands inside it — the flight-recorder postmortem then
        # names this exact span as in flight
        with obs_trace.span("worker.offer"):
            # chaos hook: lets a harness kill the worker at an exact
            # stream position ("crash at the Nth offer") — one flag
            # check when off
            faults.failpoint("worker.offer")
            stream = self.punctuate == "stream"
            now_ms = 0 if stream else int(self.clock() * 1000)
            try:
                uuid, point = self.formatter.format(message)
            except Exception:
                self.parse_failures += 1
                if self.parse_failures % 1000 == 1:
                    logger.warning("Could not parse message: %r",
                                   message[:200])
                return
            if self.uuid_filter is not None and not self.uuid_filter(uuid):
                self.skipped_other_host += 1
                return
            if stream:
                now_ms = self._advance(point.time)
            self.batcher.process(uuid, point, now_ms)
            self.processed += 1
            if self.processed % 10000 == 0:
                logger.info("Processed %d messages", self.processed)
            self.maybe_punctuate()
            # streaming backpressure (ISSUE 15): when the submit-latency
            # EWMA or requeue depth crosses its threshold, the offer
            # loop BLOCKS (bounded) before the next message — the
            # slowdown propagates upstream instead of memory absorbing
            # it. Real wall sleep on purpose: injected replay clocks
            # must not defeat flow control.
            delay = self.batcher.offer_delay()
            if delay > 0.0:
                metrics.count("backpressure.delays")
                time.sleep(delay)

    def _stream_clock(self) -> float:
        return self._stream_now if self._stream_now is not None else 0.0

    def _advance(self, t: float) -> int:
        """Stream time past probe time ``t``; returns it in ms."""
        t = float(t)
        if self._stream_now is None:
            self._last_flush = self._last_evict = t
            self._last_report_flush = t
            self._stream_now = t
        elif t > self._stream_now:
            self._stream_now = t
        return int(self._stream_now * 1000)

    def maybe_punctuate(self, force: bool = False) -> None:
        now = self.clock()
        flushed = False
        if self.batcher.pending and (
                force or now - self._last_report_flush
                >= self.report_flush_interval_s):
            self.batcher.flush_pending()
            self._last_report_flush = now
        if force or (now - self._last_evict) * 1000 >= 2 * self.session_gap_ms:
            self.batcher.punctuate(int(now * 1000))
            self._last_evict = now
            flushed = True
        if force or now - self._last_flush >= self.flush_interval_s:
            with metrics.timer("stream.tiles"):
                self._flush_tiles()
            self._last_flush = now
            flushed = True
        if self.state is not None:
            # tiles just egressed (an external side effect) — snapshot
            # NOW, else a crash would restore and re-emit them. A failed
            # snapshot degrades (wider replay window, counted) instead
            # of killing the stream; the flush-epoch marker keeps the
            # widened window duplicate-free.
            try:
                if flushed:
                    self.state.save(self.batcher, self.anonymiser)
                else:
                    self.state.maybe_save(self.batcher, self.anonymiser)
            except Exception as e:
                metrics.count("state.save.fail")
                logger.error("state snapshot failed (will retry): %s", e)
        if self.drainer is not None:
            self.drainer.maybe_drain()
        if self.heartbeat_s > 0:
            self._maybe_heartbeat()

    def _maybe_heartbeat(self) -> None:
        """Emit the structured heartbeat when its wall interval elapsed:
        one JSON line with throughput, in-flight state, the flush epoch
        and the circuit state — the reference's 10k counter, made
        chartable. Paced by message arrival (the worker is single
        threaded by design): a stalled input emits none, which IS the
        stall signal — no background thread to lock against."""
        now = time.monotonic()
        dt = now - self._hb_last
        if dt < self.heartbeat_s:
            return
        rate = (self.processed - self._hb_processed) / dt if dt > 0 else 0.0
        self._hb_last = now
        self._hb_processed = self.processed
        waste = profiler.padding_waste()
        logger.info("heartbeat %s", json.dumps({
            "processed": self.processed,
            "msgs_per_s": round(rate, 1),
            "batches_in_flight": len(self.batcher.store),
            "pending_reports": len(self.batcher.pending),
            "flush_epoch": self.anonymiser.flush_epoch,
            "circuit": self.circuit_probe() if self.circuit_probe
            else None,
            # which guarded domains are serving degraded right now
            # (open breakers; [] = all closed, None = no probe wired)
            "degraded": self.degraded_probe() if self.degraded_probe
            else None,
            # dead-letter backlog gauges: a drain stall shows up as a
            # growing spool long before the disk alarm does. THIS
            # worker's roots, not the module globals — in a multi-worker
            # process every heartbeat must gauge its own spools (TTL-
            # cached: a full spool must not turn heartbeats into walks)
            "deadletter": {
                "tiles": spool_mod.backlog_cached(self._tile_spool),
                "traces": spool_mod.backlog_cached(self._trace_spool)},
            "parse_failures": self.parse_failures,
            # delta-pressure backlog (cached last compactor sweep):
            # partitions over pressure waiting on background compaction
            "datastore_backlog": self.compactor.pending()
            if self.compactor is not None else None,
            # the device-compute vitals (obs/profiler.py): padding the
            # fixed buckets pay, compile churn, shadow-oracle verdicts
            "padding_waste": round(waste, 4) if waste is not None
            else None,
            "compile_count": profiler.compile_count(),
            "shadow_mismatches": profiler.shadow_mismatches(),
            # load management (ISSUE 15): the process-wide degradation-
            # ladder state and this worker's backpressure sensors — a
            # pressured fleet is visible in the heartbeat stream long
            # before a dashboard is opened
            "pressure": _pressure_level(),
            "backpressure": self.batcher.governor.snapshot(),
            # carried incremental decode state (ISSUE 19): live traces,
            # state bytes vs budget, eviction/fallback counters — the
            # per-worker view of match.incremental.* (None = no probe
            # wired, or the table was never built)
            "incremental": self.incremental_probe()
            if self.incremental_probe else None,
            # which map build this worker matches against (None until
            # the owner wires it): swaps are visible per worker in the
            # heartbeat stream, not just on the serving tier's /health
            "map_version": self.map_version,
        }, separators=(",", ":")))

    def _flush_tiles(self) -> None:
        """Tile egress bracketed by durability barriers.

        Pre-egress snapshot: the reports that fed this flush already
        trimmed their batches; making those trims durable BEFORE the
        tiles leave the process means a crash anywhere in the flush
        cannot restore untrimmed batches that would re-report (and so
        re-emit) segments the sink already has. Post-egress, the
        committed-epoch marker lands AFTER the sink ack and BEFORE the
        next snapshot, so restore can tell "flushed then crashed"
        (skip the epoch) from "crashed mid-flush" (re-emit under the
        same deterministic names — an overwrite, not a duplicate)."""
        # the barrier only matters when something can actually egress —
        # an idle interval must not pay a full fsync'd snapshot
        if self.state is not None and self.anonymiser.slice_of:
            try:
                self.state.save(self.batcher, self.anonymiser)
            except Exception as e:
                metrics.count("state.save.fail")
                logger.error("pre-flush snapshot failed (flushing "
                             "anyway): %s", e)
        epoch = self.anonymiser.flush_epoch
        written = self.anonymiser.punctuate()
        # chaos hook: THE window the flush-epoch machinery exists for —
        # tiles at the sink, nothing durable about it yet
        faults.failpoint("worker.post_egress")
        # only a flush that fully reached the sink commits its epoch: a
        # partial/failed egress leaves the marker behind so a restore
        # retries the epoch (failed tiles are in the dead-letter spool
        # either way), and an empty flush skips the fsync entirely
        if self.state is not None and written > 0:
            try:
                self.state.commit_epoch(epoch)
            except Exception as e:
                # degraded: a restore would re-emit this epoch under the
                # same deterministic names (overwrite, not duplicate)
                metrics.count("state.epoch_commit.fail")
                logger.error("flush-epoch commit failed for %d: %s",
                             epoch, e)

    def drain(self) -> None:
        """End of stream: evict every open batch, then stop in
        dependency order (ISSUE 10) — JOIN the shadow-accuracy pool and
        give the dead-letter replayer a final drain + PAUSE before the
        final flush, so no thread outlives the spool/datastore handles
        that flush is about to release. The drain_now still runs before
        the flush so replayed traces' segments make this last flush
        instead of stranding in the spool."""
        self.batcher.punctuate(int(self.clock() * 1000) + 10 * self.session_gap_ms)
        # shadow-oracle jobs read the profiler ring and count metrics;
        # a straggler completing after the final flush would race the
        # teardown below it. Joined here, it simply cannot.
        profiler.shutdown_shadow_pool()
        if self.drainer is not None:
            self.drainer.drain_now()
            # paused, not just drained: a maybe_punctuate from a late
            # caller must not re-enter the submit path or the sink
            self.drainer.pause()
        with metrics.timer("stream.tiles"):
            self._flush_tiles()
        if self.compactor is not None:
            # signal + JOIN after the final flush (its deltas were the
            # last pressure source): no compaction thread may outlive
            # the store handles this worker is about to release
            self.compactor.stop()
        if self.state is not None:
            self.state.save(self.batcher, self.anonymiser)
        if self.datastore is not None:
            # hand the writer lease back on a CLEAN exit: the
            # successor then acquires a vacant lease instead of
            # "stealing" from a dead pid on every routine restart —
            # steal counters/warnings stay a crash signal
            self.datastore.lease.release()

    def run(self, messages: Iterable[str],
            duration_s: Optional[float] = None) -> None:
        """Offer every message, then drain. ``duration_s`` bounds the
        run on the wall clock (or the injected one)."""
        clock = self._wall_clock
        deadline = clock() + duration_s if duration_s else None
        offered = 0
        with metrics.timer("stream.ingest"):
            try:
                for message in messages:
                    self.offer(message)
                    offered += 1
                    if deadline is not None and clock() > deadline:
                        break
            except Exception as e:
                # an unhandled exception is about to kill the stream:
                # leave a postmortem naming the span that was in flight
                flightrec.dump("worker.exception", {"error": repr(e)})
                raise
            finally:
                metrics.count("stream.points", offered)
            self.drain()


def inprocess_worker(matcher, formatter: Formatter, anonymiser: Anonymiser,
                     state_file: Optional[str] = None,
                     state_interval: float = 30.0,
                     **options) -> StreamWorker:
    """A :class:`StreamWorker` that matches in this process, as the CLI
    builds it without ``--reporter-url``: ``matcher`` behind a
    ``ReporterService``; report-ready sessions through its
    ``report_incremental`` (the carried-state advance, O(K) device work
    per appended point), which sends every window it declines through
    the windowed ``report_many`` in one batch; a session's carried state
    dropped when the session gap evicts it; the breaker, open-domain and
    carried-state probes for the heartbeat; the graph's map version; the
    matcher's quarantine in this worker's trace spool; and, with
    ``state_file``, a snapshot store that carries the incremental table.
    ``options`` are :class:`StreamWorker`'s own keywords. The CLI and the
    benchmark's stream cell both build their worker here."""
    from ..service.server import ReporterService
    service = ReporterService(matcher)

    def incremental_probe():
        t = matcher._incremental_table
        return t.gauge() if t is not None else None

    # session-gap eviction drops the session's carried decode state
    # with it — AFTER its final relaxed-threshold report flushed
    def on_evict(uuid):
        t = matcher._incremental_table
        if t is not None:
            t.evict(uuid, "session gap")

    state = None
    if state_file:
        from .state import StateStore
        # snapshot v3 provider: restore must BUILD the table (frames in
        # the snapshot need somewhere to live); save uses the property
        # too — constructing the empty table is dict bookkeeping only
        state = StateStore(state_file, interval_s=state_interval,
                           incremental=lambda: matcher.incremental_table)
    worker = StreamWorker(
        formatter, inproc_submitter(service), anonymiser, state=state,
        submit_many=service.report_incremental,
        circuit_probe=lambda: matcher.circuit.state,
        degraded_probe=matcher.open_domains,
        incremental_probe=incremental_probe, on_evict=on_evict, **options)
    # the heartbeat carries the graph's content-derived map version (an
    # HTTP split can't know the remote matcher's build). The tee's
    # ledger keys stay unversioned here: only a map-version owner (the
    # city registry's swap machinery) stamps the store, so a single-map
    # worker's crash-replay dedupe is byte-compatible with
    # pre-versioning spools
    try:
        from ..graph.version import map_version
        worker.map_version = map_version(matcher.net)
    except Exception:
        pass
    # poisoned-trace quarantine lands in THIS worker's trace spool
    # (explicit beats the last-writer-wins module global — see
    # StreamWorker.__init__)
    matcher.quarantine_spool = worker._trace_spool
    return worker


def resolve_uuid_filter(mode: str, bootstrap: Optional[str]):
    """Decide the multi-host uuid ownership filter.

    The sha1 filter makes N workers reading one SHARED unpartitioned
    stream process each uuid exactly once — Kafka's keyed-partition
    contract without Kafka (parallel.multihost). But when the input IS a
    Kafka consumer group (``bootstrap`` set), the group already
    partitions messages across workers; composing the sha1 filter on top
    silently drops ~(N-1)/N of each worker's share (the round-1..3
    composition bug). So: ``auto`` = filter on for shared inputs, OFF
    under a consumer group; ``on``/``off`` force it (``on`` is for
    unkeyed topics, where group partitioning does not follow uuid —
    with a loud warning).
    """
    from ..parallel import host_uuid_filter
    if mode == "off" or (mode == "auto" and bootstrap):
        return None
    uuid_filter = host_uuid_filter()
    if bootstrap and uuid_filter is not None:
        logger.warning(
            "--uuid-filter=on with a Kafka consumer group: unless the "
            "topic is unkeyed, group partitioning x sha1 filter drops "
            "most messages on every worker")
    return uuid_filter


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="reporter-stream",
        description="TPU-native streaming reporter worker")
    parser.add_argument("-f", "--formatter", required=True,
                        help="one-string formatter config (see README)")
    parser.add_argument("-u", "--reporter-url",
                        help="matcher service URL; omit to match in-process")
    parser.add_argument("--graph",
                        help="RoadNetwork .npz for in-process matching")
    parser.add_argument("-m", "--mode", default="auto")
    parser.add_argument("-r", "--reports", default="0,1")
    parser.add_argument("-x", "--transitions", default="0,1")
    parser.add_argument("-p", "--privacy", type=int, required=True)
    parser.add_argument("-q", "--quantisation", type=int, required=True)
    parser.add_argument("-i", "--flush-interval", type=int, required=True)
    parser.add_argument("-s", "--source", required=True)
    parser.add_argument("-o", "--output-location", required=True)
    parser.add_argument("-d", "--duration", type=int)
    parser.add_argument("--input", default="-",
                        help="flat file to replay, '-' for stdin")
    parser.add_argument("-b", "--bootstrap", help="Kafka bootstrap servers")
    parser.add_argument("--uuid-filter", choices=("auto", "on", "off"),
                        default="auto",
                        help="multi-host uuid ownership filter: auto = on "
                        "for shared unpartitioned inputs, OFF when a Kafka "
                        "consumer group already partitions (--bootstrap); "
                        "on/off force it")
    parser.add_argument("-t", "--topics",
                        help="comma-separated topics; first is raw input")
    parser.add_argument("--state-file",
                        help="durable state snapshot path; restored on "
                             "start, saved every --state-interval seconds")
    parser.add_argument("--state-interval", type=float, default=30.0)
    parser.add_argument("--report-flush-interval", type=float, default=1.0,
                        help="wall-clock bound (s) on how long a "
                             "threshold-crossed session waits for a "
                             "batched report flush; a huge value makes "
                             "flush boundaries a pure function of the "
                             "stream (deterministic replays/chaos runs)")
    parser.add_argument("--punctuate", choices=PUNCTUATE, default="wall",
                        help="clock of the report flush, the session-gap "
                             "eviction and the tile flush: wall, or "
                             "stream time (the largest probe time "
                             "offered so far), for backlog replays that "
                             "must form the windows and tiles the live "
                             "stream did")
    parser.add_argument("--datastore",
                        help="local histogram-store directory: every "
                             "flushed tile is ALSO aggregated in-process "
                             "(zero serialisation) so /histogram queries "
                             "work without a separate ingest step")
    parser.add_argument("--datastore-max-deltas", type=int, default=None,
                        help="background compaction: compact partitions "
                             "holding more than N uncompacted deltas "
                             "(paced thread off the flush path, "
                             "REPORTER_TPU_COMPACT_INTERVAL_S)")
    parser.add_argument("--datastore-max-delta-bytes", type=int,
                        default=None,
                        help="background compaction: compact partitions "
                             "whose uncompacted deltas exceed B bytes")
    parser.add_argument("--deadletter",
                        help="directory spooling tile bodies whose egress "
                             "failed (default <output>/.deadletter for "
                             "file sinks, <tmpdir>/reporter_tpu_deadletter "
                             "for remote); replay with: datastore ingest "
                             "--delete")
    args = parser.parse_args(argv)
    if not args.reporter_url and not args.graph:
        parser.error("--graph is required for in-process matching")

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")

    # pin the JAX platform up front: only the in-process matching path
    # touches devices, but deciding here keeps backend init out of the
    # first batch flush
    if not args.reporter_url:
        from ..utils.runtime import ensure_backend
        ensure_backend()

    # joins a multi-host JAX job when REPORTER_TPU_COORDINATOR etc. are
    # set; single-host no-op otherwise
    from ..parallel import init_multihost
    init_multihost()
    uuid_filter = resolve_uuid_filter(args.uuid_filter, args.bootstrap)

    tee = None
    datastore = None
    compactor = None
    if args.datastore:
        from ..datastore import BackgroundCompactor, LocalDatastore
        datastore = LocalDatastore(args.datastore)
        # freshness tier (datastore/freshness.py): the tee's ingest
        # records every flushed delta into the recent-delta overlay +
        # change feed, so /histogram?window= and /feed subscribers see
        # a probe within one tee cycle (REPORTER_TPU_FRESHNESS=0 opts
        # out and this is a no-op)
        datastore.enable_freshness()
        max_deltas = args.datastore_max_deltas
        max_bytes = args.datastore_max_delta_bytes
        inline_deltas = inline_bytes = None
        if max_deltas is not None or max_bytes is not None:
            # the pressure policy moved OFF the flush hot path: the tee
            # ingest below never compacts inline any more — the paced
            # background thread (lease-gated, so exactly one compactor
            # per store root across processes) sweeps instead. EXCEPT
            # when the operator disabled the thread
            # (REPORTER_TPU_COMPACT_INTERVAL_S=0): the explicit
            # --datastore-max-deltas flags must still mean something,
            # so the tee falls back to the old inline pressure check
            from ..datastore.compactor import compact_interval_s
            if compact_interval_s() > 0:
                compactor = BackgroundCompactor(datastore,
                                                max_deltas=max_deltas,
                                                max_delta_bytes=max_bytes)
            else:
                inline_deltas, inline_bytes = max_deltas, max_bytes

        def tee(_tile, segments, ingest_key=None, _ds=datastore,
                _n=inline_deltas, _b=inline_bytes):
            # ingest_key is the flush identity the anonymiser stamps —
            # the exactly-once ledger key that makes crash-replayed
            # flushes idempotent. A LeaseHeldElsewhere (another process
            # owns the store) propagates like any tee failure: the
            # anonymiser spools the tile body for later replay
            return _ds.ingest_segments(segments, max_deltas=_n,
                                       max_delta_bytes=_b,
                                       ingest_key=ingest_key)

    anonymiser = Anonymiser(
        TileSink(args.output_location, deadletter=args.deadletter),
        args.privacy, args.quantisation, mode=args.mode,
        source=args.source, tee=tee)
    options = dict(
        mode=args.mode, reports=args.reports, transitions=args.transitions,
        flush_interval_s=args.flush_interval, uuid_filter=uuid_filter,
        report_flush_interval_s=args.report_flush_interval,
        datastore=datastore, compactor=compactor,
        punctuate=args.punctuate)
    formatter = Formatter.from_config(args.formatter)
    if args.reporter_url:
        state = None
        if args.state_file:
            from .state import StateStore
            state = StateStore(args.state_file,
                               interval_s=args.state_interval)
        # HTTP split: one POST per trace (the reference's only mode)
        worker = StreamWorker(formatter, http_submitter(args.reporter_url),
                              anonymiser, state=state, **options)
    else:
        from ..graph.network import RoadNetwork
        from ..matcher import SegmentMatcher
        worker = inprocess_worker(
            SegmentMatcher(net=RoadNetwork.load(args.graph)), formatter,
            anonymiser, state_file=args.state_file,
            state_interval=args.state_interval, **options)

    # the flat-file input is opened under an ExitStack so the handle
    # closes on every exit path (drain, exception, --duration cut-off)
    with contextlib.ExitStack() as stack:
        if args.bootstrap:
            from .broker import KafkaBroker
            broker = KafkaBroker(args.bootstrap)
            raw_topic = (args.topics or "raw").split(",")[0]
            messages = (value.decode()
                        for _key, value in broker.consume(raw_topic))
        elif args.input == "-":
            messages = (line for line in sys.stdin)
        else:
            messages = stack.enter_context(open(args.input))
        worker.run(messages, duration_s=args.duration)
    logger.info("Done: %d processed, %d parse failures",
                worker.processed, worker.parse_failures)
    return 0


if __name__ == "__main__":
    sys.exit(main())
