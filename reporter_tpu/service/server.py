"""The /report HTTP service.

Drop-in replacement for the reference matcher service
(reference: py/reporter_service.py): same URL surface
(``GET /report?json=...`` and ``POST /report`` with a JSON body), same
request validation and error bodies, same response schema — so the Java
streaming worker (Batch.java:56-72) and the test harnesses work unchanged.

What changed underneath: instead of a thread pool with one C++ matcher per
thread, request threads hand their trace to a :class:`BatchDispatcher`
which batches concurrent requests into single vmapped TPU decodes.

Environment knobs honoured from the reference deployment:
  THRESHOLD_SEC            trailing holdback (reference: :55-58)
  THREAD_POOL_COUNT /      server thread count
  THREAD_POOL_MULTIPLIER   (reference: :37-40)
plus new batching knobs MATCH_BATCH_MAX (traces per device batch) and
MATCH_BATCH_WAIT_MS (flush latency bound).

Run:  python -m reporter_tpu.service.server <config.json> <host:port>
"""
from __future__ import annotations

import json
import multiprocessing
import os
import sys
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..core.tracebatch import points_to_columns
from ..matcher import Configure, SegmentMatcher
from ..obs import trace as obs_trace
from ..utils import metrics
from . import admission
from .dispatch import BatchDispatcher
from .report import report, report_wire

# /report is the reference's only action (reporter_service.py:26);
# /stats is new — a metrics snapshot (counters + stage-timer
# histograms: count/total/mean/max + p50/p95/p99);
# /metrics is the same registry in Prometheus exposition text;
# /histogram is the datastore query surface (datastore/query.py), live
# when the service was built with a datastore attached;
# /health is the failure-domain probe: graph, native runtime vs numpy
# fallback, circuit state, SLO breaches, datastore reachability —
# 200 or 503;
# /profile is the device-level profiler (obs/profiler.py): per-shape
# compile telemetry, per-chunk bucket-occupancy wide events, shadow-
# accuracy verdicts;
# /feed is the change-feed long-poll (datastore/feed.py): bbox
# subscribers block on a monotone cursor instead of polling /histogram
ACTIONS = {"report", "stats", "metrics", "histogram", "health",
           "profile", "feed"}

#: pressure-ladder rung at which /feed sheds subscribers (429 +
#: Retry-After): rung 2 (shed_trace) — one rung BEFORE the ladder
#: starts coarsening the decode shapes (coarse_buckets), so feed
#: fan-out is always the first load dropped
FEED_SHED_LEVEL = 2


class ReporterService:
    """Owns the matcher + dispatcher; shared by all handler threads."""

    def __init__(self, matcher: SegmentMatcher,
                 threshold_sec: int | None = None,
                 max_batch: int | None = None,
                 max_wait_ms: float | None = None,
                 datastore=None, cities=None):
        self.matcher = matcher
        # optional LocalDatastore serving /histogram (None = 503 there)
        self.datastore = datastore
        # optional CityRegistry (service/cities.py): requests carrying
        # a ``city`` key route to that city's resident stack (loaded
        # through the byte-budgeted LRU with route-memo pre-warm);
        # requests without one serve this default matcher/datastore
        self.cities = cities
        # optional BackgroundCompactor attached by the owning harness/
        # worker — /health surfaces its delta-pressure backlog gauge
        self.compactor = None
        from ..utils.runtime import _env_float, _env_int
        self.threshold_sec = threshold_sec if threshold_sec is not None else \
            _env_int("THRESHOLD_SEC", 15)
        # MATCH_BATCH_MAX default scales with the decode mesh
        # (matcher.match_batch_default: >=2 decode chunks per drained
        # batch, so N devices never sit idle behind a half-chunk flush)
        from ..matcher.matcher import match_batch_default
        self.dispatcher = BatchDispatcher(
            matcher.match_many,
            max_batch=max_batch or _env_int("MATCH_BATCH_MAX", 0)
            or match_batch_default(),
            max_wait_ms=max_wait_ms if max_wait_ms is not None else
            _env_float("MATCH_BATCH_WAIT_MS", 20.0),
            idle_grace_ms=_env_float("MATCH_BATCH_GRACE_MS", 2.0))
        # pre-fork identity ("p<index>:<pid>", set by service/prefork.py
        # worker_main): stamped on responses as X-Reporter-Proc so load
        # tests and the chaos harness can see which worker answered;
        # None (single-process mode) adds no header
        self.proc_tag: str | None = None
        # SLO-driven admission control (service/admission.py, ISSUE 15):
        # armed by REPORTER_TPU_ADMISSION — the /report front door sheds
        # with 429 + Retry-After before work is queued, and feeds the
        # process-wide pressure ladder. None = admit everything (the
        # pre-ISSUE-15 behaviour; the bounded dispatcher queue is still
        # the loud backstop).
        self.admission = admission.AdmissionGate(self.dispatcher) \
            if admission.armed() else None
        # collector pauses on /stats and in profiler traces
        # (process.gc.pause), one hook per process
        metrics.install_gc_timer()

    def handle(self, trace: dict) -> "tuple[int, str | bytes | memoryview]":
        """Validate + match + report; (status, body). The 200 body is
        BYTES (a memoryview of the chunk buffer on the native wire
        path) — _respond writes it to the socket as is; error bodies
        stay str. Validation messages mirror the reference
        (reporter_service.py:209-245)."""
        routed = self._route(trace, "handle")
        if routed is not None:
            return routed
        if trace.get("uuid") is None:
            return 400, '{"error":"uuid is required"}'
        try:
            trace["trace"][1]
        except Exception:
            return 400, ('{"error":"trace must be a non zero length array of '
                         'object each of which must have at least lat, lon '
                         'and time"}')
        try:
            report_levels = set(trace["match_options"]["report_levels"])
        except Exception:
            return 400, '{"error":"match_options must include report_levels array"}'
        try:
            transition_levels = set(trace["match_options"]["transition_levels"])
        except Exception:
            return 400, '{"error":"match_options must include transition_levels array"}'
        try:
            # columnarise the wire ONCE, in this request thread — the
            # dispatch loop and matcher never touch point dicts again
            with metrics.timer("service.columns", cpu=True):
                lat, lon, tm, acc = points_to_columns(trace["trace"])
            match = self.dispatcher.submit(
                trace, columns=(trace.get("uuid"), lat, lon, tm, acc,
                                trace.get("match_options")))
            # wire writer: the whole response body as bytes, straight
            # from the match's run columns — ONE GIL-released C call on
            # the native backend (memoryview handed to the socket with
            # no re-encode), the Python columnar writer otherwise; the
            # per-trace report/segment dicts never exist on this path
            with metrics.timer("report.serialise", cpu=True):
                return 200, report_wire(match, trace, self.threshold_sec,
                                        report_levels, transition_levels)
        except admission.Overload as e:
            # the bounded dispatcher queue shed this request (the
            # backstop behind the admission gate): 429, with the
            # computed back-off in the body — the HTTP handler lifts
            # it into the Retry-After header
            return 429, json.dumps({"error": "overloaded",
                                    "reason": e.reason,
                                    "retry_after_s": e.retry_after_s})
        except Exception as e:
            return 500, json.dumps({"error": str(e)})

    def _route(self, req: dict, method: str):
        """City routing (service/cities.py): a ``city`` key sends this
        request to that city's resident stack — loading it through the
        LRU (with route-memo pre-warm) on a miss. Returns the routed
        (status, body), an error response for an unknown city, or None
        to serve from this default stack."""
        city = req.get("city")
        if city is None:
            return None
        if self.cities is None:
            return 400, json.dumps(
                {"error": "no city registry attached; this fleet "
                          "serves a single city"})
        try:
            # acquire/release pin: the LRU may evict this city while
            # the request is in flight — the entry's dispatcher then
            # closes at our release, never underneath us
            entry = self.cities.acquire(str(city))
        except KeyError as e:
            return 400, json.dumps({"error": str(e).strip("'\"")})
        except Exception as e:
            return 500, json.dumps({"error": f"city load failed: {e}"})
        try:
            sub = {k: v for k, v in req.items() if k != "city"}
            # the routed city's OWN admission gate guards its /report
            # path: the front-door gate only watches THIS service's
            # dispatcher, and a city stack's bounded queue filling up
            # must shed city traffic — not ride on the default stack's
            # idle sensors. (The city key lives in the parsed body, so
            # city sheds are necessarily post-parse; they still happen
            # before any work is queued on the city's dispatcher.)
            gate = getattr(entry.service, "admission", None) \
                if method == "handle" else None
            if gate is not None:
                shed = gate.admit()
                if shed is not None:
                    return 429, json.dumps(
                        {"error": "overloaded", "reason": shed.reason,
                         "retry_after_s": shed.retry_after_s})
                try:
                    status, body = entry.service.handle(sub)
                finally:
                    gate.release()
            elif method == "handle":
                status, body = entry.service.handle(sub)
            else:
                return getattr(entry.service, method)(sub)
            if status == 200:
                # swap shadow capture (service/cities.py): sampled
                # admitted traffic is the corpus the dual-version
                # gate re-scores on a candidate graph at swap time.
                # getattr: registries are duck-typed (tests stub them)
                # and capture is best-effort, never request-fatal.
                observe = getattr(entry, "observe", None)
                if observe is not None:
                    observe(sub)
            return status, body
        finally:
            self.cities.release(entry)

    def histogram(self, params: dict) -> tuple[int, str]:
        """Answer a /histogram query; (status, body). ``params`` carries
        ONE of ``segment_id`` (single), ``segments`` (batched: answered
        through one ``query_many`` sweep) or ``bbox`` + ``level``
        (every resident segment of that level inside the lon/lat box),
        plus optional ``hours`` (list of hour-of-week ints),
        ``time_range`` ([t0, t1) epoch seconds, converted to the hour
        set it covers), ``percentiles``, ``window`` (freshness tier:
        ``5m``/``300s``/``inf`` — see datastore/freshness.py),
        ``viewport`` (with bbox+level: the materialised tile summaries,
        one read per covered tile), and ``city`` (multi-tenant
        routing)."""
        routed = self._route(params, "histogram")
        if routed is not None:
            return routed
        if self.datastore is None:
            return 503, ('{"error":"no datastore attached; serve with a '
                         '--datastore directory"}')
        from ..datastore import DEFAULT_PERCENTILES, hours_for_range
        if params.get("viewport"):
            if params.get("bbox") is None or params.get("level") is None:
                return 400, ('{"error":"viewport queries need bbox '
                             'and level"}')
            tier = self.datastore.enable_freshness()
            if tier is None:
                return 503, ('{"error":"freshness tier disabled '
                             '(REPORTER_TPU_FRESHNESS=0)"}')
            try:
                result = tier.viewports.summarise(
                    params["bbox"], int(params["level"]))
            except (TypeError, ValueError) as e:
                return 400, json.dumps({"error": str(e)})
            return 200, json.dumps(result, separators=(",", ":"))
        seg = params.get("segment_id")
        segs = params.get("segments")
        bbox = params.get("bbox")
        if seg is None and segs is None and bbox is None:
            return 400, ('{"error":"one of segment_id, segments or '
                         'bbox (+level) is required"}')
        hours = params.get("hours")
        if hours is None and params.get("time_range") is not None:
            try:
                t0, t1 = params["time_range"]
            except Exception:
                return 400, ('{"error":"time_range must be a [start, end) '
                             'epoch-seconds pair"}')
            hours = hours_for_range(int(t0), int(t1)).tolist()
        pcts = tuple(params.get("percentiles") or DEFAULT_PERCENTILES)
        # window=: served through the freshness overlay's store view
        # (enable the tier on demand so window=inf works in a serving
        # process that never ingests); window-less requests take the
        # exact pre-freshness path — byte-identical answers
        window = params.get("window")
        if window is not None:
            self.datastore.enable_freshness()
        # epoch pin/merge (datastore/__init__.py): map_version= pins
        # the sweep to one map build, merge=1 explicitly mixes epochs;
        # the default pins to the store's active version
        mv = params.get("map_version")
        mv = str(mv) if mv is not None else None
        merge = bool(params.get("merge"))
        try:
            if bbox is not None:
                if params.get("level") is None:
                    return 400, ('{"error":"bbox queries need a level '
                                 '(0, 1 or 2)"}')
                result = self.datastore.query_bbox(
                    bbox, int(params["level"]), hours=hours,
                    percentiles=pcts,
                    max_segments=params.get("max_segments"),
                    window=window, map_version=mv, merge=merge)
            elif segs is not None:
                result = {"results": self.datastore.query_many(
                    [int(s) for s in segs], hours=hours,
                    percentiles=pcts, window=window,
                    map_version=mv, merge=merge)}
            else:
                result = self.datastore.query(int(seg), hours=hours,
                                              percentiles=pcts,
                                              window=window,
                                              map_version=mv,
                                              merge=merge)
        except (TypeError, ValueError) as e:
            return 400, json.dumps({"error": str(e)})
        return 200, json.dumps(result, separators=(",", ":"))

    def feed(self, params: dict) -> tuple[int, str]:
        """Answer one /feed long-poll; (status, body). Sheds BEFORE
        registering a waiter — on the pressure ladder (rung >=
        ``FEED_SHED_LEVEL``: subscriber fan-out is dropped one rung
        before the match path degrades) and on the feed's own bounded
        waiter table — with 429 bodies carrying ``retry_after_s`` (the
        handler lifts it into Retry-After: PR 14's explicit-retry
        contract; a subscriber is never silently dropped)."""
        routed = self._route(params, "feed")
        if routed is not None:
            return routed
        if self.datastore is None:
            return 503, ('{"error":"no datastore attached; serve with a '
                         '--datastore directory"}')
        tier = self.datastore.enable_freshness()
        if tier is None:
            return 503, ('{"error":"freshness tier disabled '
                         '(REPORTER_TPU_FRESHNESS=0)"}')
        from ..datastore.feed import FEED_RETRY_AFTER_S, FeedOverload
        if admission.current_level() >= FEED_SHED_LEVEL:
            metrics.count("feed.shed.pressure")
            return 429, json.dumps(
                {"error": "overloaded", "reason": "pressure",
                 "retry_after_s": FEED_RETRY_AFTER_S})
        try:
            out = tier.feed.poll(
                bbox=params.get("bbox"),
                level=int(params["level"])
                if params.get("level") is not None else None,
                cursor=int(params.get("cursor", -1)),
                timeout_s=min(float(params.get("timeout", 25.0)), 60.0),
                max_events=int(params.get("max_events", 256)))
        except FeedOverload as e:
            return 429, json.dumps(
                {"error": "overloaded", "reason": e.reason,
                 "retry_after_s": e.retry_after_s})
        except (TypeError, ValueError) as e:
            return 400, json.dumps({"error": str(e)})
        return 200, json.dumps(out, separators=(",", ":"))

    def health(self) -> tuple[int, str]:
        """Liveness + degradation probe; (status, JSON body).

        200 means fully serving: graph loaded and the datastore (when
        attached) reachable. 503 flags a degraded domain a load balancer
        should rotate away from: the native-prep circuit OPEN (still
        serving, via the numpy fallback, but slower), a stage whose p99
        breaches its ``REPORTER_TPU_SLO_MS`` budget (working, but over
        latency budget), or the datastore erroring. The body always
        enumerates every domain either way.
        """
        from ..obs import profiler, slo
        from ..utils import faults, spool
        m = self.matcher
        circuit = m.circuit.snapshot()
        open_domains = m.open_domains()
        try:
            from ..graph.version import map_version as _map_version
            graph_version = _map_version(m.net) if m.net is not None \
                else None
        except Exception:
            graph_version = None
        body = {
            "graph": {"loaded": m.net is not None,
                      "nodes": int(m.net.num_nodes),
                      "edges": int(m.net.num_edges),
                      # content-derived map identity (graph/version.py)
                      # of the DEFAULT stack; per-city versions live in
                      # the cities block below
                      "map_version": graph_version},
            "native": {"status": "native" if m.runtime is not None
                       else "fallback"},
            "circuit": circuit,
            # every guarded hot-path domain by name (ISSUE 9): which
            # breakers are open (serving via their fallback) and each
            # domain's full breaker state — a load balancer rotates on
            # "open", an operator reads "domains" to see which stage
            "degraded": {"open": open_domains,
                         "domains": m.circuit_snapshots()},
            # dead-letter backlog gauges (worker-registered spool roots;
            # zeros when this process runs no worker): a drain stall is
            # visible here long before the disk fills
            "deadletter": spool.backlog_snapshot(),
            "faults": faults.active_spec(),
            # shadow-decode verdicts (informational here; budget the
            # decode.shadow.mismatch_ratio histogram via
            # REPORTER_TPU_SLO_MS to make a mismatch rate flip 503)
            "shadow": profiler.shadow_stats(),
        }
        # carried-state gauge (matcher/incremental.py): table occupancy
        # vs its byte budget, lag bound, eviction/fallback/reset
        # counters — zeros until the first incremental report builds the
        # table (batch-only deployments never pay for it)
        from ..matcher import incremental as _inc
        body["incremental"] = {
            "enabled": _inc.incremental_enabled()
            and not _inc.pressure_shed()}
        if m._incremental_table is not None:
            body["incremental"].update(m._incremental_table.gauge())
        # load-management view (ISSUE 15): the degradation-ladder state
        # plus — when the gate is armed — its live sensors and per-
        # reason shed counters. Informational: a shedding service is
        # doing its job, not failing; the ladder's rungs each have
        # their own degraded signals above. health() doubles as the
        # idle-period ladder tick so a service that stopped receiving
        # traffic still steps back up.
        if self.admission is not None:
            self.admission.tick()
        body["pressure"] = admission.pressure_snapshot()
        body["admission"] = self.admission.snapshot() \
            if self.admission is not None else {"armed": False}
        healthy = True
        if open_domains:
            healthy = False
        slo_check = slo.check()
        body["slo"] = {"targets": {k: round(v * 1000.0, 3) for k, v
                                   in slo_check["targets"].items()},
                       "breaches": slo_check["breaches"]}
        if slo_check["breaches"]:
            healthy = False
        if self.datastore is None:
            body["datastore"] = {"status": "absent"}
        else:
            try:
                stats = self.datastore.stats()
                body["datastore"] = {"status": "ok",
                                     "partitions": stats["partitions"],
                                     "rows": stats["rows"],
                                     # writer-lease holder view: which
                                     # pid owns mutations on this store
                                     # root right now (multi-process
                                     # serving shares the root)
                                     "lease": self.datastore.lease
                                     .snapshot()}
            except Exception as e:
                body["datastore"] = {"status": "error", "error": str(e)}
                healthy = False
        if self.compactor is not None:
            # delta-pressure backlog gauge (cached last sweep): a
            # growing backlog means compaction is falling behind the
            # tee — visible here long before queries slow down
            body["compaction"] = self.compactor.pending()
        if self.datastore is not None \
                and getattr(self.datastore, "freshness", None) is not None:
            # freshness-tier gauges: overlay occupancy vs its byte
            # budget (evictions here mean the window is effectively
            # shorter than configured), feed waiters/sheds, viewport
            # materialisation counts
            body["freshness"] = self.datastore.freshness.snapshot()
        if self.cities is not None:
            body["cities"] = self.cities.snapshot()
        body["status"] = "ok" if healthy else "degraded"
        return (200 if healthy else 503,
                json.dumps(body, separators=(",", ":")))

    def report_incremental(self, traces) -> list:
        """:meth:`report_many` with the carried-state fast path: traces
        the incremental matcher serves (O(K) device work per appended
        point) skip the whole-window dispatcher round trip; every slot
        it declines — no uuid, kill switch, pressure shed, open
        circuit, parity fallback, eviction — rides ONE batched
        :meth:`report_many` call instead. The per-slot reports are
        byte-identical either way (the incremental path's match dicts
        are pinned to the batch oracle), so callers cannot tell which
        path served them except by latency and the
        ``match.incremental.*`` counters."""
        import logging
        from ..core.tracebatch import as_trace_batch
        log = logging.getLogger("reporter_tpu.service")
        tb = as_trace_batch(traces)
        try:
            matches = self.matcher.match_incremental(tb)
        except Exception as e:   # defensive: match_incremental degrades
            log.error("incremental match failed (%s); the batch path "
                      "serves this flush", e)
            matches = [None] * len(tb)
        unserved = [i for i, mt in enumerate(matches) if mt is None]
        if len(unserved) == len(tb):
            return self.report_many(tb)
        out: list = [None] * len(tb)
        if unserved:
            for j, rep in zip(unserved, self.report_many(tb.gather(unserved))):
                out[j] = rep
        for i, mt in enumerate(matches):
            if mt is None:
                continue
            trace = tb[i]
            try:
                opts = trace["match_options"]
                out[i] = report(mt, trace, self.threshold_sec,
                                set(opts["report_levels"]),
                                set(opts["transition_levels"]))
            except Exception as e:
                log.error("report build failed for %s: %s",
                          trace.get("uuid"), e)
        return out

    def report_many(self, traces) -> list:
        """Match + report a whole list — or a columnar
        :class:`TraceBatch` — in ONE dispatcher round trip (one device
        batch up to MATCH_BATCH_MAX); returns parsed report dicts, None
        for a trace that failed — a one-batch failure costs only that
        batch's traces, and the cause is logged. The streaming worker's
        in-process flush path — no per-trace HTTP, no per-trace JSON, no
        point dicts."""
        import logging
        log = logging.getLogger("reporter_tpu.service")
        matches = self.dispatcher.submit_many(traces,
                                              return_exceptions=True)
        out = []
        for trace, match in zip(traces, matches):
            if isinstance(match, Exception):
                log.error("batched match failed for %s: %s",
                          trace.get("uuid"), match)
                out.append(None)
                continue
            try:
                opts = trace["match_options"]
                out.append(report(match, trace, self.threshold_sec,
                                  set(opts["report_levels"]),
                                  set(opts["transition_levels"])))
            except Exception as e:
                log.error("report build failed for %s: %s",
                          trace.get("uuid"), e)
                out.append(None)
        return out


def make_handler(service: ReporterService):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def parse_request(self) -> bool:
            # the request line and headers, every action
            with metrics.timer("service.headers", cpu=True):
                return super().parse_request()

        def _parse(self, post: bool) -> dict:
            split = urllib.parse.urlsplit(self.path)
            if split.path.split("/")[-1] not in ACTIONS:
                raise ValueError("Try a valid action: " + str(sorted(ACTIONS)))
            if post:
                length = int(self.headers.get("Content-Length", 0))
                return json.loads(self.rfile.read(length).decode("utf-8"))
            params = urllib.parse.parse_qs(split.query)
            if "json" in params:
                return json.loads(params["json"][0])
            raise ValueError("No json provided")

        def _respond(self, code: int, body,
                     content_type: str = "application/json;charset=utf-8",
                     headers=None):
            # str bodies encode here; bytes/memoryview bodies (the
            # native wire writer's buffer) go to the socket AS IS —
            # the zero-copy handoff the C writer exists for
            raw = body.encode("utf-8") if isinstance(body, str) else body
            # one request per connection, like the reference's HTTP/1.0
            # service — keep-alive would pin a bounded pool slot idle
            self.close_connection = True
            self.send_response(code)
            self.send_header("Access-Control-Allow-Origin", "*")
            if service.proc_tag is not None:
                self.send_header("X-Reporter-Proc", service.proc_tag)
            for key, value in (headers or {}).items():
                self.send_header(key, value)
            self.send_header("Content-type", content_type)
            self.send_header("Content-length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

        def _respond_shed(self, code: int, body, retry_after_s=None):
            """A load-shed response: every 429 carries the computed
            ``Retry-After`` — the contract utils/http.py clients
            already honour. Callers that hold the Overload pass the
            seconds directly (the front-door shed path is HOT under
            overload); only bodies built deeper in the stack (the
            dispatcher backstop, a routed city's gate) pay the parse."""
            retry = retry_after_s
            if retry is None:
                try:
                    retry = json.loads(body).get("retry_after_s")
                except Exception:
                    pass
            headers = {"Retry-After": str(int(retry))} \
                if retry is not None else None
            self._respond(code, body, headers=headers)

        def _parse_histogram(self, post: bool) -> dict:
            """Histogram params: JSON body / ``json=`` like /report, or
            bare GET query params (``segment_id=…&hours=7-9``)."""
            params = urllib.parse.parse_qs(
                urllib.parse.urlsplit(self.path).query)
            if post or "json" in params:
                return self._parse(post)
            out: dict = {}
            if "segment_id" in params:
                out["segment_id"] = int(params["segment_id"][0])
            # repeated segment params: ?segment=A&segment=B&... —
            # served through ONE query_many sweep
            if "segment" in params:
                out["segments"] = [int(s) for s in params["segment"]]
            # ?bbox=min_lon,min_lat,max_lon,max_lat&level=L
            if "bbox" in params:
                out["bbox"] = [float(v) for v
                               in params["bbox"][0].split(",")]
            if "level" in params:
                out["level"] = int(params["level"][0])
            if "max_segments" in params:
                out["max_segments"] = int(params["max_segments"][0])
            if "city" in params:
                out["city"] = params["city"][0]
            if "hours" in params:
                from ..datastore import parse_hours_spec
                out["hours"] = parse_hours_spec(params["hours"][0])
            if "t0" in params and "t1" in params:
                out["time_range"] = [int(params["t0"][0]),
                                     int(params["t1"][0])]
            if "percentiles" in params:
                out["percentiles"] = [
                    float(p) for p in params["percentiles"][0].split(",") if p]
            # ?window=5m|300s|inf — freshness-tier staleness bound
            if "window" in params:
                out["window"] = params["window"][0]
            # ?map_version=abc123def456 — pin the sweep to one map
            # epoch; ?merge=1 — explicit opt-in to sweep every epoch
            # (default pins to the store's active version)
            if "map_version" in params:
                out["map_version"] = params["map_version"][0]
            if "merge" in params:
                out["merge"] = params["merge"][0].lower() \
                    not in ("", "0", "off", "false")
            # ?viewport=1 — materialised tile summaries for bbox+level
            if "viewport" in params:
                out["viewport"] = params["viewport"][0].lower() \
                    not in ("", "0", "off", "false")
            return out

        def _parse_feed(self, post: bool) -> dict:
            """Feed params: JSON body / ``json=`` like /report, or bare
            GET query params (``bbox=…&level=L&cursor=N&timeout=S``)."""
            params = urllib.parse.parse_qs(
                urllib.parse.urlsplit(self.path).query)
            if post or "json" in params:
                return self._parse(post)
            out: dict = {}
            if "bbox" in params:
                out["bbox"] = [float(v) for v
                               in params["bbox"][0].split(",")]
            for key in ("level", "cursor", "max_events"):
                if key in params:
                    out[key] = int(params[key][0])
            if "timeout" in params:
                out["timeout"] = float(params["timeout"][0])
            if "city" in params:
                out["city"] = params["city"][0]
            return out

        def _do(self, post: bool):
            split = urllib.parse.urlsplit(self.path)
            action = split.path.split("/")[-1]
            if action == "stats":
                # the wire writer owns the rounding (snapshot() reports
                # raw floats so sub-µs stages don't collapse to 0.0)
                self._respond(200, json.dumps(metrics.snapshot_rounded()))
                return
            if action == "metrics":
                from ..obs import prom
                self._respond(200, prom.render(),
                              content_type=prom.CONTENT_TYPE)
                return
            if action == "profile":
                from ..obs import profiler
                prof = profiler.snapshot()
                # the load-management view rides /profile too: sheds
                # per reason, in-flight, per-dispatcher queue gauges
                # (prof["queue_depths"]) and the ladder state
                prof["pressure"] = admission.pressure_snapshot()
                if service.admission is not None:
                    prof["admission"] = service.admission.snapshot()
                if service.cities is not None:
                    # the residency table with each city's route-memo
                    # counters + warmed_pairs: the cold-start pair a
                    # pre-warm assertion reads (serve_smoke)
                    prof["cities"] = service.cities.snapshot()
                self._respond(200, json.dumps(prof,
                                              separators=(",", ":")))
                return
            if action == "health":
                code, body = service.health()
                if code != 200:
                    metrics.count(f"service.errors.{code}")
                self._respond(code, body)
                return
            if action == "histogram":
                try:
                    params = self._parse_histogram(post)
                except Exception as e:
                    self._respond(400, json.dumps({"error": str(e)}))
                    return
                metrics.count("service.requests.histogram")
                with metrics.timer("service.histogram"):
                    code, body = service.histogram(params)
                if code != 200:
                    metrics.count(f"service.errors.{code}")
                self._respond(code, body)
                return
            if action == "feed":
                try:
                    params = self._parse_feed(post)
                except Exception as e:
                    self._respond(400, json.dumps({"error": str(e)}))
                    return
                metrics.count("service.requests.feed")
                code, body = service.feed(params)
                if code != 200:
                    metrics.count(f"service.errors.{code}")
                if code == 429:
                    # _respond_shed lifts retry_after_s from the body
                    # into Retry-After: every shed subscriber gets the
                    # explicit retry signal (PR 14 contract)
                    self._respond_shed(code, body)
                else:
                    self._respond(code, body)
                return
            # the admission gate (ISSUE 15): shed BEFORE the body is
            # even parsed — a 429 must cost headers, not work. The
            # in-flight slot an admit holds is released when the
            # response is written, whatever its status.
            gate = service.admission
            if gate is not None:
                shed = gate.admit()
                if shed is not None:
                    metrics.count("service.errors.429")
                    self._respond_shed(
                        429, json.dumps(
                            {"error": "overloaded",
                             "reason": shed.reason,
                             "retry_after_s": shed.retry_after_s}),
                        retry_after_s=shed.retry_after_s)
                    return
            # ?trace=1 debug flag: arm tracing for this request and ship
            # the request's span tree (Chrome/Perfetto trace-event JSON)
            # alongside the report body. The pressure ladder's
            # shed_trace rung refuses the flag under sustained overload
            # (the report still serves — only the debug tree is shed).
            qs = urllib.parse.parse_qs(split.query)
            # same falsy spellings as REPORTER_TPU_TRACE env parsing
            want_trace = qs.get("trace", ["0"])[0].lower() \
                not in ("", "0", "off", "false")
            if want_trace and not admission.allow_request_trace():
                metrics.count("pressure.trace_suppressed")
                want_trace = False
            if want_trace:
                obs_trace.force_begin()
            try:
                # the root span: one per /report request, covering parse
                # -> dispatch -> match -> serialisation, so every stage
                # span below it shares the request's trace_id
                with obs_trace.span("service.request") as root:
                    try:
                        with metrics.timer("service.parse", cpu=True):
                            trace = self._parse(post)
                    except Exception as e:
                        self._respond(400, json.dumps({"error": str(e)}))
                        return
                    metrics.count("service.requests")
                    with metrics.timer("service.handle"):
                        code, body = service.handle(trace)
                if want_trace and code == 200:
                    if not isinstance(body, str):  # native wire bytes
                        body = bytes(body).decode("utf-8")
                    body = ('{"report":' + body + ',"trace":'
                            + json.dumps(obs_trace.export_trace(root),
                                         separators=(",", ":")) + "}")
            finally:
                if want_trace:
                    obs_trace.force_end()
                if gate is not None:
                    gate.release()
            if code != 200:
                metrics.count(f"service.errors.{code}")
            with metrics.timer("service.respond"):
                if code == 429:
                    self._respond_shed(code, body)
                else:
                    self._respond(code, body)

        def do_GET(self):
            self._do(False)

        def do_POST(self):
            self._do(True)

        def log_message(self, fmt, *args):  # quiet by default
            pass

    return Handler


class BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a cap on concurrent handler threads.

    The reference sizes its pool at THREAD_POOL_COUNT or
    THREAD_POOL_MULTIPLIER x cpus because each of its threads runs a
    CPU-heavy C++ matcher (reference: reporter_service.py:37-40). Both
    env knobs are honoured here, but the DEFAULT is a flat 64: in this
    architecture handler threads only parse JSON and then *wait* on the
    micro-batching dispatcher — they are IO-bound, and sizing them by
    cpu count serialises requests on small hosts (measured on one core:
    a pool of 1 turned every batch into a batch of ONE and added the
    full dispatcher wait to every request — 44 req/s where the matcher
    itself does thousands/s). Excess connections queue in the listen
    backlog until a slot frees."""

    daemon_threads = True
    # accepts queue here while all pool slots are busy
    request_queue_size = 128

    def __init__(self, addr, handler, pool_size: int | None = None):
        if pool_size is None:
            from ..utils.runtime import _env_int
            count = _env_int("THREAD_POOL_COUNT", 0)
            mult = _env_int("THREAD_POOL_MULTIPLIER", 0)
            pool_size = count or \
                (mult * multiprocessing.cpu_count() if mult else 64)
        self._slots = threading.BoundedSemaphore(max(1, pool_size))
        super().__init__(addr, handler)

    def process_request(self, request, client_address):
        self._slots.acquire()
        super().process_request(request, client_address)

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            self._slots.release()


def make_server(service: ReporterService, host: str, port: int,
                pool_size: int | None = None,
                reuse_port: bool = False) -> BoundedThreadingHTTPServer:
    """The ONE server constructor every entry point goes through, so
    the THREAD_POOL_COUNT/_MULTIPLIER knobs apply uniformly (the old
    ``__main__`` path constructed the server directly and silently
    ignored them). ``reuse_port`` binds with SO_REUSEPORT — the
    pre-fork multi-process mode's shared-port primitive."""
    cls = ReusePortThreadingHTTPServer if reuse_port \
        else BoundedThreadingHTTPServer
    return cls((host, port), make_handler(service), pool_size)


class ReusePortThreadingHTTPServer(BoundedThreadingHTTPServer):
    """BoundedThreadingHTTPServer binding with ``SO_REUSEPORT``: N
    processes each bind the same (host, port) and the kernel spreads
    accepted connections across them — the pre-fork serving mode's
    listener (service/prefork.py). Manual setsockopt: socketserver only
    grew ``allow_reuse_port`` in Python 3.11."""

    def server_bind(self):
        import socket
        self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        super().server_bind()


def serve(service: ReporterService, host: str, port: int,
          pool_size: int | None = None) -> BoundedThreadingHTTPServer:
    httpd = make_server(service, host, port, pool_size)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return httpd


def make_service(conf: dict) -> ReporterService:
    """Build the service a config names (``Configure(conf)`` first) —
    what ``python -m reporter_tpu serve`` runs, and chip_smoke.py too.
    Everything heavyweight — backend init, graph load, native build,
    datastore mount — happens HERE, which in multi-process mode runs
    post-fork in each worker: children never inherit device handles,
    native worker pools or dispatcher threads."""
    # a "datastore" key in the config (or REPORTER_TPU_DATASTORE)
    # mounts a local histogram store under /histogram
    datastore = None
    ds_root = os.environ.get("REPORTER_TPU_DATASTORE") \
        or conf.get("datastore")
    if ds_root:
        from ..datastore import LocalDatastore
        datastore = LocalDatastore(ds_root)

    # pin the JAX platform before the first decode
    # (REPORTER_TPU_PLATFORM=cpu|tpu, unset = JAX's default)
    from ..utils.runtime import ensure_backend
    ensure_backend()

    # joins a multi-host JAX job when REPORTER_TPU_COORDINATOR etc.
    # are set; single-host no-op otherwise
    from ..parallel import init_multihost
    init_multihost()
    # a "cities" map in the config mounts the multi-tenant registry
    # (service/cities.py): city=-tagged requests route through the
    # byte-budgeted residency LRU with route-memo pre-warm
    cities = None
    if conf.get("cities"):
        from .cities import CityRegistry
        cities = CityRegistry(conf["cities"])
    service = ReporterService(SegmentMatcher(), datastore=datastore,
                              cities=cities)
    # stamp the default stack's store with its graph epoch, the
    # same contract as a CityRegistry load (cities.py): the
    # /histogram default pin must track the graph THIS process
    # serves — without the stamp a restart forgets the active
    # epoch and the default query silently mixes map builds
    if datastore is not None \
            and service.matcher.net is not None:
        from ..graph.version import map_version as _mv
        try:
            datastore.set_map_version(_mv(service.matcher.net))
        except Exception as e:
            sys.stderr.write(f"map version stamp failed: {e}\n")
    return service


def main(argv=None):
    argv = list(argv if argv is not None else sys.argv[1:])
    # --procs N: pre-fork multi-process serving (SO_REUSEPORT); the
    # REPORTER_TPU_SERVICE_PROCS env knob is the no-CLI spelling
    procs = None
    if "--procs" in argv:
        i = argv.index("--procs")
        try:
            procs = int(argv[i + 1])
        except (IndexError, ValueError):
            sys.stderr.write("--procs needs an integer\n")
            return 1
        del argv[i:i + 2]
    if procs is None:
        from ..utils.runtime import _env_int
        procs = _env_int("REPORTER_TPU_SERVICE_PROCS", 1)
    if len(argv) < 2:
        sys.stderr.write(
            "usage: python -m reporter_tpu.service.server <config.json> "
            "<host:port> [--procs N]\n")
        return 1
    try:
        with open(argv[0]) as f:
            conf = json.load(f)
        Configure(conf)
        host, port = argv[1].split("/")[-1].split(":")
        port = int(port)
    except Exception as e:
        sys.stderr.write(f"Problem with config file: {e}\n")
        return 1

    if procs > 1:
        from .prefork import serve_prefork
        return serve_prefork(lambda: make_service(conf), host, port, procs)

    service = make_service(conf)
    httpd = make_server(service, host, port)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        httpd.server_close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
