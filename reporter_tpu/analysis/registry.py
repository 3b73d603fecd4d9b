"""The cross-layer contract registry: the single source of truth.

PR 5 made correctness depend on hand-maintained cross-layer lists — the
``REPORTER_TPU_*`` env knobs README documents, the metric names /stats
consumers grep for, the ``KNOWN_SITES`` failpoint table chaos scenarios
arm, and the tmp-write -> fsync -> ``os.replace`` commit discipline of
every durable path. None of them were machine-checked, and five knobs
had already drifted out of README by PR 6. This module is the fix: ONE
declarative registry the contract passes (durability, lockgraph,
registry_drift, fault_coverage) verify both sides of — code that uses
an unregistered name fails lint, and a registry entry nothing uses
fails lint too, so the lists can neither rot nor bloat.

Adding a knob / metric / fault site is a three-line change: the code,
this registry, and (for knobs) README's table — and ``tools/lint.py
--contracts-only`` tells you which line you forgot.

Like the rest of the analysis package this imports nothing beyond the
stdlib, so the lint stage needs no accelerator stack.
"""
from __future__ import annotations

from typing import Dict, Tuple

# ---- environment knobs -----------------------------------------------------
# Every REPORTER_TPU_* name any code in reporter_tpu/, tools/ or
# bench.py (or the C++ runtime) reads. Two-sided with the code
# (registry_drift KN001) and with README's knob table (KN002).
ENV_KNOBS: Dict[str, str] = {
    "REPORTER_TPU_PLATFORM": "cpu|tpu backend pin (unset = JAX default)",
    "REPORTER_TPU_VIRTUAL_DEVICES": "virtual CPU device count",
    "REPORTER_TPU_DECODE": "decode backend: scan|assoc|pallas",
    "REPORTER_TPU_DECODE_CHUNK": "traces per decode dispatch",
    "REPORTER_TPU_PIPELINE": "device-lane overlap on/off",
    "REPORTER_TPU_PREP_THREADS": "native prep worker-pool width",
    "REPORTER_TPU_PREP_TIMINGS": "print native prep phase times",
    "REPORTER_TPU_ROUTE_MEMO": "native cross-call route-pair memo size",
    "REPORTER_TPU_ROUTE_DEVICE": "device route-cost kernel on/off",
    "REPORTER_TPU_ROUTE_PRUNE_SIGMA": "candidate prune margin, sigma mult",
    "REPORTER_TPU_ROUTE_HOPS": "device relax sweep cap (0 = auto)",
    "REPORTER_TPU_ROUTE_CACHE_NODES": "numpy route cache: node entries",
    "REPORTER_TPU_ROUTE_CACHE_PAIRS": "numpy route cache: pair entries",
    "REPORTER_TPU_WIRE": "f16|f32 device wire format",
    "REPORTER_TPU_WIRE_NATIVE": "/report wire writer: auto|off",
    "REPORTER_TPU_SERVICE_PROCS": "pre-fork service worker count",
    "REPORTER_TPU_SHARD": "multi-device mesh decode on/off",
    "REPORTER_TPU_DECODE_SHARD": "decode mesh: auto|on|off",
    "REPORTER_TPU_DEVICE_SLICE": "this process's local-device subset",
    "REPORTER_TPU_SEQ_SHARDS": "sequence-parallel time-axis shards",
    "REPORTER_TPU_BUCKETS": "decode length-bucket ladder",
    "REPORTER_TPU_COORDINATOR": "jax.distributed rendezvous address",
    "REPORTER_TPU_NUM_PROCESSES": "jax.distributed process count",
    "REPORTER_TPU_PROCESS_ID": "jax.distributed process id",
    "REPORTER_TPU_DATASTORE": "histogram-store dir served on /histogram",
    "REPORTER_TPU_DATASTORE_HANDLES": "partition mmap-handle LRU size",
    "REPORTER_TPU_STORE_LEASE_S": "cross-process writer-lease TTL (0 off)",
    "REPORTER_TPU_COMPACT_INTERVAL_S": "background compactor pace (s)",
    "REPORTER_TPU_CITY_BUDGET_MB": "multi-city residency LRU byte budget",
    "REPORTER_TPU_NATIVE": "C++ host runtime: auto|off (prep kill switch)",
    "REPORTER_TPU_NATIVE_LIB": "prebuilt .so override (sanitizers/CI)",
    "REPORTER_TPU_FAULTS": "deterministic failpoint spec",
    "REPORTER_TPU_CIRCUIT_THRESHOLD": "errors that open the breaker",
    "REPORTER_TPU_CIRCUIT_COOLDOWN_S": "breaker cooldown before a probe",
    "REPORTER_TPU_SUBMIT_RETRIES": "submit requeues before dead-letter",
    "REPORTER_TPU_WRITER_ID": "writer tag in epoch tile names",
    "REPORTER_TPU_CHAOS_REQUIRE_NATIVE": "chaos: missing native = fail",
    "REPORTER_TPU_TRACE": "request tracing on/off (spans + export)",
    "REPORTER_TPU_SLO_MS": "per-stage p99 budgets flipping /health",
    "REPORTER_TPU_FLIGHTREC": "flight-recorder dump dir (0 disables)",
    "REPORTER_TPU_HEARTBEAT_S": "worker heartbeat interval (0 off)",
    "REPORTER_TPU_SHADOW_SAMPLE": "shadow-oracle decode sample fraction",
    "REPORTER_TPU_PROFILE_EVENTS": "profiler wide-event ring capacity",
    "REPORTER_TPU_DEADLETTER_MAX_MB": "spool byte cap (oldest shed)",
    "REPORTER_TPU_REPLAY_INTERVAL_S": "dead-letter drain pace (0 off)",
    "REPORTER_TPU_REPLAY_ATTEMPTS": "replays before .quarantine",
    "REPORTER_TPU_INGEST_LEDGER_MAX": "ingest-ledger keys/partition",
    "REPORTER_TPU_LOCKCHECK": "runtime lock witness: 1 arms, raw = A/B leg",
    "REPORTER_TPU_LOCKCHECK_HOLD_MS": "RC002 long-hold threshold (ms)",
    "REPORTER_TPU_RACEFUZZ": "schedule-fuzz spec seed[:prob][@max_us]",
    "REPORTER_TPU_ADMISSION": "SLO-driven admission gate on /report",
    "REPORTER_TPU_QUEUE_MAX": "dispatcher queue bound, traces (0 = off)",
    "REPORTER_TPU_QUEUE_POLICY": "full-queue shed policy: reject|oldest",
    "REPORTER_TPU_INFLIGHT_MAX": "admitted in-flight cap (0 = derived)",
    "REPORTER_TPU_BATCH_LATENCY_MS": "per-batch latency budget (0 = fixed)",
    "REPORTER_TPU_PRESSURE_HOLD_S": "degradation-ladder hysteresis dwell",
    "REPORTER_TPU_BACKPRESSURE": "streaming offer backpressure (0 = off)",
    "REPORTER_TPU_BACKPRESSURE_LATENCY_S": "submit-EWMA slow-down threshold",
    "REPORTER_TPU_FRESHNESS": "freshness tier (overlay/feed/viewport) gate",
    "REPORTER_TPU_FRESHNESS_MB": "recent-delta overlay byte budget (MB)",
    "REPORTER_TPU_FRESHNESS_WAITERS": "/feed long-poll waiter cap (shed past)",
    "REPORTER_TPU_FRESHNESS_POLL_S": "feed store-watch pace (cross-process)",
    "REPORTER_TPU_INCREMENTAL": "incremental matcher path (off disables)",
    "REPORTER_TPU_INCREMENTAL_LAG": "fixed-lag commit bound, kept points",
    "REPORTER_TPU_INCREMENTAL_MB": "carried-state table byte budget (MB)",
    "REPORTER_TPU_SWAP_SAMPLE": "swap shadow capture sampling fraction",
    "REPORTER_TPU_SWAP_AGREEMENT": "swap flip floor: min shadow agreement",
    "REPORTER_TPU_SWAP_WINDOW": "swap capture-ring size (requests)",
    "REPORTER_TPU_SWAP_FORCE": "override: flip below the agreement floor",
}

# ---- metric names ----------------------------------------------------------
# Every name the code passes to the metrics layer (utils.metrics
# count/timer/observe). Entries ending in ``*`` are prefix patterns for
# dynamically-suffixed families (f-string call sites); pattern entries
# are exempt from the dead-entry check (MT002) precisely because their
# call sites are dynamic — exact entries must have a literal somewhere.
METRICS: Dict[str, str] = {
    # matcher
    "matcher.prep": "host prep per chunk (timer)",
    "matcher.decode_dispatch": "jit call + async d2h start (timer)",
    "matcher.decode_wait": "d2h wait (timer)",
    "matcher.assemble": "run walk + column conversion (timer)",
    "matcher.circuit.*": "breaker transitions + degraded-chunk counts",
    "prep.phase.*": "native prep phase split (candidates/select/routes)",
    "route.device.*": "device route kernel: chunks/sources/fallbacks",
    # numpy route cache
    "route.cache.node_hits": "route cache: node-level hits",
    "route.cache.node_misses": "route cache: node-level misses",
    "route.cache.pair_hits": "route cache: pair-level hits",
    "route.cache.pair_misses": "route cache: pair-level misses",
    # service
    "service.requests": "/report requests",
    # native wire writer (service/wire.py)
    "wire.native": "responses emitted by the C-level writer",
    "wire.fallback": "responses served by the Python columnar writer",
    "wire.errors": "native writer faults (degraded to Python, not 500)",
    "wire.circuit.*": "wire-writer breaker transitions/probes",
    # pre-fork supervisor (service/prefork.py)
    "service.procs.spawned": "worker processes forked at startup",
    "service.procs.deaths": "worker exits outside shutdown",
    "service.procs.restarts": "workers restarted into their slot",
    "service.procs.worker_start": "per-worker post-fork service builds",
    "service.requests.histogram": "/histogram requests",
    "service.headers": "request line + header parse, every action (timer)",
    "service.headers.cpu": "service.headers thread CPU, sampled (timer)",
    "service.headers.cpu_wall": "service.headers wall, same sample (timer)",
    "service.parse": "/report body read + JSON decode (timer)",
    "service.parse.cpu": "service.parse thread CPU, sampled (timer)",
    "service.parse.cpu_wall": "service.parse wall, same sample (timer)",
    "service.columns": "/report points -> column arrays (timer)",
    "service.columns.cpu": "service.columns thread CPU, sampled (timer)",
    "service.columns.cpu_wall": "service.columns wall, same sample (timer)",
    "report.serialise": "/report response body writer (timer)",
    "report.serialise.cpu": "report.serialise thread CPU, sampled (timer)",
    "report.serialise.cpu_wall": "report.serialise wall, same sample "
                                 "(timer)",
    "service.respond": "/report status, headers + body to the socket "
                       "(timer)",
    "service.handle": "/report handling (timer)",
    "service.histogram": "/histogram handling (timer)",
    "service.errors.*": "error responses by status code",
    "dispatch.batches": "micro-batches dispatched",
    "dispatch.traces": "traces dispatched",
    "dispatch.match_many": "batched match call (timer)",
    "dispatch.queue_wait": "a trace's enqueue -> its batch's match call "
                           "(timer)",
    "dispatch.idle": "dispatch loop blocked on an empty queue (timer)",
    "dispatch.fill": "dispatch loop collecting a batch to its flush "
                     "(timer)",
    "dispatch.errors": "dispatch loop errors",
    # load management (ISSUE 15)
    "dispatch.queue.*": "bounded-queue sheds: rejected/evicted/waits",
    "admission.*": "gate verdicts: admitted + shed.{queue,slo,inflight}",
    "pressure.*": "degradation-ladder transitions + rung effects",
    "batch.latency.*": "EWMA flush model: per-trace latency + caps",
    "backpressure.*": "streaming flow control: delays + sheds",
    "slo.malformed": "malformed SLO specs ignored (fail-open, counted)",
    "decode.shadow.suppressed": "shadow chunks skipped by the ladder",
    # streaming
    "egress.ok": "tile egress successes",
    "egress.fail": "tile egress failures",
    "egress.deadletter": "tile bodies spooled to the dead letter",
    "batch.requeued": "failed submits requeued under budget",
    "batch.dropped": "batches dropped after budget exhaustion",
    "batch.deadletter": "trace JSON spooled for replay",
    "state.epoch_skipped": "restores that skipped a committed epoch",
    "state.save.fail": "failed state snapshots (degraded)",
    "state.epoch_commit.fail": "failed epoch-marker commits (degraded)",
    "matcher.assemble.quarantined": "poisoned traces spooled, chunk kept",
    "deadletter.shed": "spool entries shed by the byte cap (oldest)",
    "replay.traces.ok": "dead-letter traces re-submitted successfully",
    "replay.traces.fail": "dead-letter trace replay attempts that failed",
    "replay.tiles.ok": "dead-letter tiles re-egressed successfully",
    "replay.tiles.fail": "dead-letter tile replay attempts that failed",
    "replay.quarantined": "dead-letter entries moved to .quarantine",
    "stream.points": "messages offered to the streaming worker's run",
    "stream.windows": "report windows submitted and answered, any path",
    "stream.ingest": "the worker's run: message loop and drain (timer)",
    "stream.report_flush": "one batched report submit, pending or "
                           "eviction flush (timer)",
    "stream.tiles": "one tile flush: snapshot barrier, cull, egress "
                    "(timer)",
    # pipeline
    "pipeline.gather": "backfill stage 1 (timer)",
    "pipeline.gather.lines": "stage 1 probe lines read",
    "pipeline.gather.probes": "stage 1 probes kept and appended to "
                              "their shard's columnar block",
    "pipeline.gather.uuid_hashes": "stage 1 shard hashes, one per distinct "
                                   "uuid of a part",
    "pipeline.gather.hour_epochs": "stage 1 hour epochs, one per distinct "
                                   "hour of a part",
    "pipeline.gather_failures": "part files lost to a parse error or a "
                                "dead gather worker",
    "pipeline.match": "backfill stage 2 (timer)",
    "pipeline.report": "backfill stage 3 (timer)",
    "pipeline.report.tiles": "stage 3 tile files written",
    "pipeline.report.rows": "stage 3 rows the privacy cull kept, written",
    "pipeline.tile_failures": "tile files lost to an error or a dead "
                              "stage 3 worker",
    "pipeline.worker_starts": "stage 1 and 3 worker processes started",
    "pipeline.worker_starts.preloaded": "of those, the workers forked from "
                                        "the preloaded fork server",
    "pipeline.worker_boot": "a stage's worker start-up: its first start to "
                            "its last worker entering its work (timer)",
    "pipeline.parse": "stage 2 shard blocks read, one (uuid, time) sort "
                      "and window cut (timer)",
    "pipeline.parse.probes": "stage 2 records read from shard blocks; "
                             "equals pipeline.gather.probes on a sound run",
    "pipeline.parse.torn_blocks": "stage 2 shard blocks skipped as cut "
                                  "short at a file's end",
    "pipeline.match_many": "stage 2 device batch call (timer)",
    "pipeline.rows": "stage 2 report, seam filter and tile-row append "
                     "(timer); tile rows appended (counter)",
    "pipeline.windows": "matcher windows sent to the device",
    "pipeline.points": "probe points in those windows",
    "pipeline.batches": "stage 2 match_many calls",
    "pipeline.seam_dropped": "reports dropped at chunk seams (duplicates)",
    "pipeline.batch_failures": "device batches lost to a match_many error",
    "pipeline.report_failures": "windows lost to a report() error",
    # datastore
    "datastore.ingest.parse": "tile CSV parse (timer)",
    "datastore.ingest.bad_rows": "dropped malformed tile rows",
    "datastore.ingest.dir": "directory replay (timer)",
    "datastore.ingest.quarantined": "tiles quarantined mid-ingest",
    "datastore.ingest.files": "tile files replayed",
    "datastore.ingest.deduped": "ledger-deduped appends (exactly-once)",
    "datastore.ingest.ledger_evicted": "ledger keys aged out by the cap",
    "datastore.tee.deadletter": "tee-failed tiles spooled (sink was ok)",
    "datastore.query": "histogram query (timer)",
    "datastore.aggregate": "observation aggregation (timer)",
    "datastore.aggregate.rows": "observation rows aggregated",
    "datastore.store.append": "segment commit (timer)",
    "datastore.store.compact": "compaction pass (timer)",
    "datastore.store.auto_compactions": "pressure-policy compactions",
    "datastore.store.stale_commits": "seq-fence aborts (lease lapsed)",
    "datastore.query.cache.hits": "partition-handle LRU hits",
    "datastore.query.cache.misses": "partition-handle LRU misses",
    "datastore.query.many": "batched multi-segment query sweep (timer)",
    "datastore.query.bbox": "bbox query: resolve + batched sweep (timer)",
    "datastore.query.batched_segments": "segments served via query_many",
    "datastore.lease.*": "writer-lease acquires/renewals/steals/rejections",
    "datastore.compactor.*": "background compaction passes/compactions",
    "datastore.city.*": "city-residency LRU loads/hits/evictions",
    # map lifecycle (ISSUE 20: graph/version.py + cities.swap)
    "swap.flips": "hot swaps that flipped routing to the new map",
    "swap.refusals": "swaps refused (budget pin or shadow agreement)",
    "swap.shadow.*": "dual-version gate: sampled/checks/agree/mismatch",
    "datastore.epoch.*": "map-version epochs: stamped segments, pinned/"
                         "merged queries, feed epoch events",
    "datastore.profile.exports": "route-memo profile artifacts written",
    "datastore.profile.warmed_pairs": "memo pairs pre-warmed at city load",
    # freshness tier (ISSUE 18: datastore/freshness.py + feed.py)
    "overlay.*": "recent-delta overlay: records/deduped/evicted/committed",
    "feed.*": "change feed: events/polls/delivered/shed/timeouts/watch",
    "viewport.*": "materialised viewport summaries: refreshes/queries",
    "service.requests.feed": "/feed long-poll requests",
    # observability
    "flightrec.dumps": "flight-recorder postmortems written",
    "process.gc.pause": "one collector pass, any generation (timer)",
    # device-level profiler (obs/profiler.py)
    "decode.compile.count": "decode dispatches that paid an XLA compile",
    "decode.compile.recompiles": "same-shape recompiles (storm signal)",
    "decode.compile": "XLA compile seconds per episode (timer)",
    "decode.dispatch.first": "compiling-dispatch wall (timer)",
    "decode.dispatch.steady": "steady-state dispatch wall (timer)",
    "decode.occupancy.*": "per-bucket occupancy ratio histograms",
    "decode.shard.*": "mesh-path decode chunks + rows fanned across it",
    "decode.chunks": "decode chunks planned (one prep, dispatch, assembly)",
    "decode.shadow.chunks": "chunks shadow-decoded via the numpy oracle",
    "decode.shadow.sampled": "traces shadow-decoded via the numpy oracle",
    "decode.shadow.mismatch": "shadow decodes scoring off the oracle",
    "decode.shadow.mismatch_ratio": "per-chunk mismatch ratio (timer)",
    "decode.shadow.dropped": "shadow chunks shed (sampler backlogged)",
    "decode.shadow.errors": "shadow decode failures (chunk skipped)",
    "profile.chunks": "wide events recorded",
    # incremental matcher (ISSUE 19: matcher/incremental.py)
    "match.incremental.*": "carried-state path: steps/commits/matches/"
                           "state_bytes/evictions/fallbacks/resets/"
                           "shadow checks + the advance/decode timers",
    # runtime concurrency witness (analysis/racecheck.py)
    "racecheck.findings": "witness/audit findings, all RC rules",
    "racecheck.*": "per-rule finding counts (RC001-RC004)",
}

# ---- failpoint sites -------------------------------------------------------
# Mirrors utils/faults.py KNOWN_SITES (fault_coverage FP001 verifies the
# two stay identical) and adds the coverage contract: every site must
# have >=1 failpoint() call site (FP002) and be exercised by a chaos
# scenario or a tests/test_faults.py case (FP003).
FAULT_SITES: Dict[str, str] = {
    "native.prep": "native prep error -> circuit breaker + fallback",
    "decode.dispatch": "device decode error -> numpy-oracle fallback",
    "matcher.assemble": "assembly error -> per-trace scalar + quarantine",
    "matcher.submit": "report submit failure -> bounded requeue",
    "egress.http": "tile sink failure -> dead-letter spool",
    "datastore.commit": "segment commit failure -> caller quarantine",
    "datastore.compact": "crash mid-compaction -> orphan dir, manifest "
                         "untorn; next holder re-compacts",
    "datastore.lease": "lease I/O failure -> mutation refused (spooled)",
    "state.save": "snapshot failure -> degraded (wider replay window)",
    "worker.offer": "crash at an exact stream position",
    "worker.post_egress": "crash between sink ack and epoch marker",
    "wire.native": "native wire-writer fault -> Python writer, same bytes",
    "admission.gate": "gate/sensor failure -> fail OPEN (admit), counted",
    "route.device": "device route fill error -> native re-prep with routes",
    "match.incremental.commit": "crash/error at a fixed-lag commit -> "
                                "carried state dropped, batch-path replay",
    "city.swap": "crash/error in the widest swap window (candidate "
                 "loaded+gated, old still serving) -> old map keeps "
                 "serving; crash recovery proves exactly-once across "
                 "epochs",
}

# ---- durable layout roots --------------------------------------------------
# Modules whose writes land under durable roots (the datastore
# partition layout, the state snapshot + epoch marker, tile-sink
# output and the dead-letter spools). The durability pass (DUR001-003)
# holds every write here to the fsio commit protocol.
DURABLE_MODULES: Tuple[str, ...] = (
    "reporter_tpu/datastore/store.py",
    "reporter_tpu/datastore/ingest.py",
    # the per-city route-memo profile commits into the store root (a
    # torn profile would warm garbage); the .lease file is deliberately
    # NOT here — it is flock-serialised coordination state whose torn
    # body safely parses as "no holder" (datastore/lease.py docstring)
    "reporter_tpu/datastore/profile.py",
    "reporter_tpu/streaming/state.py",
    "reporter_tpu/streaming/anonymiser.py",
    "reporter_tpu/utils/fsio.py",
    # the flight recorder dumps into the dead-letter layout — a torn
    # postmortem after a crash would be worse than none
    "reporter_tpu/obs/flightrec.py",
    # the shared spool layer owns every dead-letter write (torn spool
    # entries replay as truncation), and the drainer moves entries
    # within the spool roots (.quarantine)
    "reporter_tpu/utils/spool.py",
    "reporter_tpu/streaming/drainer.py",
)

# ---- epoch-marker commit ordering (DUR004) ---------------------------------
# "relpath::qualname" -> (ack_call, commit_call): in the annotated
# function, every ``commit_call`` must be reachable only AFTER an
# ``ack_call`` — the exactly-once-ish egress window (a marker committed
# before the sink acked would make restore skip an epoch the sink never
# got).
EPOCH_COMMIT_CONTRACTS: Dict[str, Tuple[str, str]] = {
    "reporter_tpu/streaming/worker.py::StreamWorker._flush_tiles":
        ("punctuate", "commit_epoch"),
}

# ---- kernel contracts (TC rules) -------------------------------------------
# "relpath::function" for every jax.jit / pallas_call entry point the
# jit_hygiene enumerator finds. Two-sided with the code (tensorcontract
# TC002): an entry here with no jit region is dead, a jit entry missing
# here is uncontracted. The abstract shape/dtype signatures themselves
# live in tools/kernel_contracts.json (regenerated by
# ``python -m reporter_tpu.analysis.tensorcontract --write``); entries
# the eval harness cannot drive stand-alone (a passed-in kernel wrapper,
# a pallas kernel body) are covered through their callers and carry no
# JSON cases.
KERNEL_CONTRACTS: Dict[str, str] = {
    "reporter_tpu/ops/route_relax.py::relax_csr":
        "multi-source bounded relaxation -> (S,N) dist/time kernels",
    "reporter_tpu/ops/route_relax.py::pair_costs":
        "route-tensor assembly -> (B,T-1,K,K) costs + max_finite",
    "reporter_tpu/ops/route_relax.py::pair_costs_packed":
        "pair_costs behind two packed h2d blobs (warm dispatch)",
    "reporter_tpu/ops/assoc_viterbi.py::viterbi_assoc_batch":
        "associative-scan decode -> (B,T) paths + (B,) scores",
    "reporter_tpu/ops/pallas_viterbi.py::viterbi_pallas_batch":
        "pallas fused decode -> (B,T) paths + (B,) scores",
    "reporter_tpu/ops/pallas_viterbi.py::_forward_kernel":
        "pallas kernel body (covered via viterbi_pallas_batch; no "
        "stand-alone eval cases)",
    "reporter_tpu/matcher/hmm.py::viterbi_decode_batch":
        "scan decode -> (B,T) paths + (B,) scores (the oracle twin)",
    "reporter_tpu/parallel/sharded.py::kernel":
        "sharded wrapper over a passed-in decode kernel (signature "
        "owned by the wrapped entry; no stand-alone eval cases)",
    "reporter_tpu/parallel/sharded.py::viterbi_assoc_batch":
        "mesh-sharded re-jit of assoc decode (signature owned by "
        "ops/assoc_viterbi.py; needs a Mesh, no stand-alone eval cases)",
    "reporter_tpu/ops/incremental.py::incremental_step_batch":
        "R-round incremental Viterbi advance -> (N,K) scores + (R,N,K) "
        "bp + (R,N) restart anchors",
}

# ---- device lanes / host-sync whitelist (DP rules) -------------------------
# DEVICE_LANES are the prep/dispatch/drain thread entry points the
# placement pass walks (the real submits go through the _lane_stage
# indirection, so structural pool-root detection cannot find them).
# SYNC_POINTS are the functions allowed to materialise device arrays on
# the host (np.asarray/.item()/float()): traversal from a lane stops
# there. Everything else reachable from a lane that synchronises is a
# DP001 — the class of bug that silently serialises the pipeline.
DEVICE_LANES: Dict[str, str] = {
    "reporter_tpu/matcher/matcher.py::SegmentMatcher._dispatch_stage":
        "dispatch lane: jit call + async d2h start",
    "reporter_tpu/matcher/matcher.py::SegmentMatcher._drain_stage":
        "drain lane: d2h wait + assembly",
    "reporter_tpu/graph/route_device.py::DeviceRouteKernel.fill_prep":
        "prep-thread route fill (native prepare_batch skip_routes path)",
}

SYNC_POINTS: Dict[str, str] = {
    "reporter_tpu/matcher/matcher.py::SegmentMatcher._drain_stage":
        "THE d2h gather: np.asarray(decoded) under matcher.decode_wait",
    "reporter_tpu/matcher/batchpad.py::PaddedBatch.finalize_wire":
        "deferred route resolve + wire-dtype decision at dispatch time",
    "reporter_tpu/graph/route_device.py::DeferredRoutes.write_back":
        "route-tensor d2h write into the prep dict (idempotent)",
}

# ---- fallback parity pairs (FB rules) --------------------------------------
# Keyed by circuit-breaker domain: every dual path (a device/native fast
# path with a byte-identical host fallback) declares its fault site, its
# kill-switch knob, and the parity test that pins the two paths equal.
# Two-sided with the code (fallback FB001/FB002): a CircuitBreaker
# domain with no pair here is an undeclared dual path, and a pair whose
# legs dangle (unknown site/knob, missing test) is a paper contract.
FALLBACK_PAIRS: Dict[str, Dict[str, str]] = {
    "matcher.circuit": {  # native prep <-> numpy prep
        "fault_site": "native.prep",
        "knob": "REPORTER_TPU_NATIVE",
        "parity_test": "tests/test_report_writer.py::"
                       "test_report_json_native_equals_fallback_bytes",
    },
    "matcher.circuit.decode": {  # device decode <-> numpy oracle
        "fault_site": "decode.dispatch",
        "knob": "REPORTER_TPU_DECODE",
        "parity_test": "tests/test_faults.py::TestDecodeDomain",
    },
    "matcher.circuit.route": {  # device routes <-> host Dijkstra
        "fault_site": "route.device",
        "knob": "REPORTER_TPU_ROUTE_DEVICE",
        "parity_test": "tests/test_route_device.py::"
                       "test_reports_byte_identical",
    },
    "wire.circuit": {  # native wire writer <-> python columnar writer
        "fault_site": "wire.native",
        "knob": "REPORTER_TPU_WIRE_NATIVE",
        "parity_test": "tests/test_report_writer.py::"
                       "test_wire_cross_path_property",
    },
    "matcher.circuit.incremental": {  # carried-state <-> windowed batch
        "fault_site": "match.incremental.commit",
        "knob": "REPORTER_TPU_INCREMENTAL",
        "parity_test": "tests/test_incremental.py::"
                       "test_incremental_matches_batch_noise_profiles",
    },
}

__all__ = ["ENV_KNOBS", "METRICS", "FAULT_SITES", "DURABLE_MODULES",
           "EPOCH_COMMIT_CONTRACTS", "KERNEL_CONTRACTS", "DEVICE_LANES",
           "SYNC_POINTS", "FALLBACK_PAIRS"]
