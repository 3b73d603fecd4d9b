"""SegmentMatcher: the framework's matcher facade.

API-compatible with the surface the reference uses from the ``valhalla``
extension module (reference: py/reporter_service.py:21,52,240 and
py/simple_reporter.py:132-133):

    Configure(config_path_or_dict)
    m = SegmentMatcher()
    match_json = m.Match(trace_json_str)

plus the batched entry point the reference lacks — ``match_many`` — which is
the TPU hot path: many traces prepared on host, decoded in one vmapped
Viterbi per chunk (a micro-batch that fits one chunk is one chunk; a
larger group is cut per padding bucket).
"""
from __future__ import annotations

import json
import logging
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import numpy as np

from ..core.tracebatch import TraceBatch, as_trace_batch
from ..graph.network import RoadNetwork
from ..graph.route import RouteCache
from ..graph.spatial import SpatialGrid
from ..obs import profiler
from ..obs import trace as obs_trace
from ..utils import faults, metrics
from ..utils import locks as _locks
from ..utils.circuit import CircuitBreaker
from .assemble import assemble_segments
from .batchpad import (_next_pow2, bucket_ladder, kept_point_count,
                       pack_batches, padded_batch_rows, prepare_batch,
                       prepare_trace, prepare_traces_numpy)
from .params import MatchParams

# process-wide configuration, mirroring valhalla.Configure's module-level
# behavior (reference: reporter_service.py:284)
_global_config: dict = {}

logger = logging.getLogger("reporter_tpu.matcher")


def _route_cache_counters() -> dict:
    """Numpy route-cache hit snapshot for a chunk's wide event (the
    fallback-path twin of the native route-pair memo stats)."""
    c = metrics.default.counter
    return {"pair_hits": c("route.cache.pair_hits"),
            "pair_misses": c("route.cache.pair_misses"),
            "node_hits": c("route.cache.node_hits"),
            "node_misses": c("route.cache.node_misses")}


def _circuit_knobs() -> tuple:
    """(threshold, cooldown_s) for the native-prep circuit breaker."""
    from ..utils.runtime import _env_float, _env_int
    return (_env_int("REPORTER_TPU_CIRCUIT_THRESHOLD", 5),
            _env_float("REPORTER_TPU_CIRCUIT_COOLDOWN_S", 30.0))


def _native_disabled() -> bool:
    """REPORTER_TPU_NATIVE=off|0|false|numpy is the matcher.circuit
    kill switch: force the numpy prep fallback even when the C++ host
    runtime is importable (incident lever; default auto-detect)."""
    return os.environ.get("REPORTER_TPU_NATIVE", "").strip().lower() \
        in ("0", "off", "false", "numpy")


def _route_device_enabled() -> bool:
    """REPORTER_TPU_ROUTE_DEVICE opts the device route kernel in (off by
    default: the host path is the battle-tested oracle, and the kernel
    only pays off where a real accelerator backs jax)."""
    return os.environ.get("REPORTER_TPU_ROUTE_DEVICE", "").strip().lower() \
        in ("1", "on", "true", "yes")


#: traces a decode chunk holds on one device with the pipeline on
#: (``_decode_chunk``'s default there), and the most traces the chunk
#: plan merges into one chunk (``SegmentMatcher._plan_chunks``)
PIPELINED_CHUNK = 128

#: padded point cells worth one decode chunk's fixed host cost, the
#: most padding a merge may add for each chunk it saves. Measured on a
#: v5e host, a chunk pays about 2.3 ms of fixed prep and a decode
#: dispatch of 3.7 ms at 16 x 128 cells against 5.1 ms at 16 x 256:
#: about 0.7 us a cell over a fixed 2.2 ms, so the fixed part is worth
#: some 6,400 cells. Two thirds of that, so a merge has to save clearly.
CHUNK_COST_CELLS = 4096


def _decode_chunk() -> int:
    """Traces per decode dispatch. REPORTER_TPU_DECODE_CHUNK forces it;
    the default follows the pipeline mode: 128 when the device lanes
    are on AND there is more than one core to overlap across (chunks
    ARE the overlap granularity), 512 otherwise — chunking buys nothing
    without real overlap, so fewer dispatches win (+17% measured on one
    core at 512 vs 128) until per-chunk tensors (route_m: 16 MB f32 at
    512) outgrow cache and memory bandwidth takes it back (1024-row
    chunks measured ~10% SLOWER than 512). The default then scales by
    the decode mesh's data-axis width: a chunk is split across all M
    devices, so per-DEVICE rows (and therefore per-device utilisation)
    only hold steady if the chunk grows with the mesh."""
    from ..utils.runtime import _env_int
    val = _env_int("REPORTER_TPU_DECODE_CHUNK", 0)
    if val:
        return max(1, val)
    if pipeline_enabled() and (os.cpu_count() or 1) > 1:
        base = PIPELINED_CHUNK
    else:
        base = 512
    from ..ops import decode_mesh_size
    return base * max(1, decode_mesh_size())


def match_batch_default() -> int:
    """Default dispatcher flush cap (service MATCH_BATCH_MAX unset): at
    least TWO decode chunks per drained batch, so the dispatch lane
    keeps >=2 chunks in flight per device while the drain lane works —
    a chunk spans the whole data mesh, so 2x the chunk is 2 chunks per
    device. PR 8's queue-depth wide events are the sensor proving the
    devices stay fed under this depth. Unsharded hosts keep the
    shipped 256: the scaling rationale is mesh utilisation, and
    quadrupling the flush cap on a single lone-CPU device would only
    grow tail latency and peak memory."""
    from ..ops import decode_mesh_size
    if decode_mesh_size() <= 1:
        return 256
    return max(256, 2 * _decode_chunk())


def _prep_workers() -> int:
    """Host-prep thread count (env-tunable; 0 disables the pool)."""
    from ..utils.runtime import _env_int
    return _env_int("REPORTER_TPU_PREP_THREADS",
                    min(32, os.cpu_count() or 1))


#: pressure-ladder last rung (service/admission.py "oracle_decode"):
#: decode serves via the per-trace numpy oracle — the same degraded
#: path the decode circuit breaker uses — keeping the device queue
#: free for the drain backlog. One global load on the hot path.
_pressure_oracle = False


def set_pressure_oracle(on: bool) -> None:
    global _pressure_oracle
    _pressure_oracle = bool(on)


def pipeline_enabled() -> bool:
    """Overlap the device lanes (decode dispatch; d2h wait + assembly)
    with host prep of later chunks. REPORTER_TPU_PIPELINE forces on/off;
    the default is platform-aware: ON wherever there is device or IO
    time to hide (any accelerator, or a multi-core CPU host where the
    GIL-releasing native assembly genuinely parallelises), OFF on a
    single-core CPU-only host, where every stage contends for the same
    core and the thread hops are a measured ~5-12% end-to-end loss.
    Results are identical either way (pinned by TestDevicePipeline)."""
    val = os.environ.get("REPORTER_TPU_PIPELINE", "").strip().lower()
    if val:
        return val not in ("0", "off", "false")
    # cpu-count short-circuits first: jax.default_backend() initialises
    # the backend as a side effect, which on TPU attaches the
    # single-client chip — a multi-core host must not pay that just to
    # read this flag
    if (os.cpu_count() or 1) > 1:
        return True
    import jax
    return jax.default_backend() != "cpu"


class RunColumns:
    """One decoded chunk's run columns as Python lists — ONE bulk
    ``.tolist()`` per column (the approved conversion idiom), shared by
    every :class:`MatchRuns` view of the chunk. This replaced the old
    per-trace ``_runs_as_lists`` slice-and-convert, which paid ~4k tiny
    tolist calls per 512-trace chunk."""

    __slots__ = ("seg_id", "internal", "start", "end", "length", "queue",
                 "begin_idx", "end_idx", "way_off", "ways", "arrays")

    def __init__(self, runs: dict):
        self.seg_id = runs["seg_id"].tolist()
        self.internal = runs["internal"].astype(bool).tolist()
        # round HERE, whole column at once (reporter-lint HP002 sweep:
        # the dict-era formatter called round() twice per run)
        start_r = np.round(runs["start"], 3)
        end_r = np.round(runs["end"], 3)
        self.start = start_r.tolist()
        self.end = end_r.tolist()
        self.length = runs["length"].tolist()
        self.queue = runs["queue"].tolist()
        self.begin_idx = runs["begin_idx"].tolist()
        self.end_idx = runs["end_idx"].tolist()
        self.way_off = runs["way_off"].tolist()
        self.ways = runs["ways"].tolist()
        # the same columns as numpy arrays (start/end already rounded),
        # in the native wire writer's column order — rt_report_json /
        # rt_render_segments_json serialise straight from these buffers
        # (service/wire.py); hand-built RunColumns-shaped test doubles
        # without this attribute take the Python writer path
        self.arrays = {
            "seg_id": runs["seg_id"], "internal": runs["internal"],
            "start": start_r, "end": end_r, "length": runs["length"],
            "queue": runs["queue"], "begin_idx": runs["begin_idx"],
            "end_idx": runs["end_idx"], "way_off": runs["way_off"],
            "ways": runs["ways"]}


def _jnum(x) -> str:
    """One JSON scalar, byte-identical to ``json.dumps(x)``: floats via
    ``float.__repr__`` (with the Infinity/NaN spellings), bools/None as
    their JSON literals, ints via ``str``."""
    if x is True:
        return "true"
    if x is False:
        return "false"
    if x is None:
        return "null"
    if isinstance(x, float):
        if x != x:
            return "NaN"
        if x == float("inf"):
            return "Infinity"
        if x == float("-inf"):
            return "-Infinity"
        return repr(x)
    return str(x)


def render_segments_json(cols: RunColumns, lo: int, hi: int,
                         mode: str) -> str:
    """Serialise run columns [lo, hi) to the reference-schema
    ``{"segments":[...],"mode":...}`` JSON — a thin dispatcher over the
    wire backend knob (``REPORTER_TPU_WIRE_NATIVE``): the C-level
    writer (native/src/host_runtime.cpp rt_render_segments_json) when
    armed and the columns carry their arrays, else the Python columnar
    writer below. Both are byte-identical to ``json.dumps`` over the
    per-run dicts the old ``_format_runs`` materialised (pinned by
    tests/test_report_writer.py)."""
    arrays = getattr(cols, "arrays", None)
    if arrays is not None:
        from ..service import wire
        out = wire.maybe_native_segments(arrays, lo, hi, mode)
        if out is not None:
            return bytes(out).decode("utf-8")
    return render_segments_json_py(cols, lo, hi, mode)


def render_segments_json_py(cols: RunColumns, lo: int, hi: int,
                            mode: str) -> str:
    """The Python columnar segments writer — the wire dispatcher's
    fallback backend, and the oracle the native writer is pinned
    against. Emits bytes from the columns and never builds a per-run
    dict. Start/end times are always finite floats here (rounded probe
    epochs / -1.0 sentinels), so they format through bare ``repr`` —
    identical bytes to json.dumps's ``float.__repr__`` path, without
    the per-value type dispatch."""
    way_off, ways = cols.way_off, cols.ways
    start, end, length = cols.start, cols.end, cols.length
    queue, internal = cols.queue, cols.internal
    begin_idx, end_idx, seg_id = cols.begin_idx, cols.end_idx, cols.seg_id
    parts = []
    for r in range(lo, hi):
        w = ",".join(map(str, ways[way_off[r]:way_off[r + 1]]))
        sid = seg_id[r]
        parts.append(
            f'{{"way_ids":[{w}],'
            f'"start_time":{start[r]!r},'
            f'"end_time":{end[r]!r},'
            f'"length":{length[r]},'
            f'"queue_length":{queue[r]},'
            f'"internal":{"true" if internal[r] else "false"},'
            f'"begin_shape_index":{begin_idx[r]},'
            f'"end_shape_index":{end_idx[r]}'
            + (f',"segment_id":{sid}}}' if sid >= 0 else "}"))
    mode_json = '"auto"' if mode == "auto" else json.dumps(mode)
    return ('{"segments":[' + ",".join(parts) + '],"mode":'
            + mode_json + "}")


class MatchRuns:
    """One trace's match result as a lazy view over its chunk's shared
    :class:`RunColumns`.

    Dict-shaped consumers (tests, the numpy-fallback comparisons, the
    worker's structured report path) see the reference-schema match dict
    through the mapping protocol below — the per-run dicts materialise
    on first structural access, via one comprehension. The hot serving
    path (``Match()`` and service ``report_json``) serialises straight
    from the columns and never triggers it. Deliberately NOT a dict
    subclass: ``json.dumps`` on a lazy dict subclass would silently
    encode the un-materialised storage; here it fails loudly instead
    (use the writers)."""

    __slots__ = ("cols", "lo", "hi", "mode", "_dict")

    def __init__(self, cols: RunColumns, lo: int, hi: int, mode: str):
        self.cols = cols
        self.lo = lo
        self.hi = hi
        self.mode = mode
        self._dict = None

    def _materialise(self) -> dict:
        d = self._dict
        if d is None:
            c, lo, hi = self.cols, self.lo, self.hi
            wo, ways = c.way_off, c.ways
            segments = [
                {"way_ids": ways[wo[r]:wo[r + 1]],
                 "start_time": c.start[r],
                 "end_time": c.end[r],
                 "length": c.length[r],
                 "queue_length": c.queue[r],
                 "internal": c.internal[r],
                 "begin_shape_index": c.begin_idx[r],
                 "end_shape_index": c.end_idx[r],
                 **({"segment_id": c.seg_id[r]}
                    if c.seg_id[r] >= 0 else {})}
                for r in range(lo, hi)]
            d = self._dict = {"segments": segments, "mode": self.mode}
        return d

    def has_runs(self) -> bool:
        """True when the match produced any segment run — an emptiness
        probe that never materialises the per-run dicts (the streaming
        batcher's trim logic only needs this bit)."""
        return self.hi > self.lo

    # -- mapping protocol (materialises) -----------------------------------
    def __getitem__(self, key):
        return self._materialise()[key]

    def __setitem__(self, key, value):
        if key == "mode":
            # report() stamps mode without needing the segment dicts
            self.mode = value
            if self._dict is not None:
                self._dict["mode"] = value
            return
        self._materialise()[key] = value

    def get(self, key, default=None):
        return self._materialise().get(key, default)

    def __contains__(self, key):
        return key in self._materialise()

    def __iter__(self):
        return iter(self._materialise())

    def __len__(self):
        return len(self._materialise())

    def keys(self):
        return self._materialise().keys()

    def values(self):
        return self._materialise().values()

    def items(self):
        return self._materialise().items()

    def __eq__(self, other):
        if isinstance(other, MatchRuns):
            other = other._materialise()
        if isinstance(other, dict):
            return self._materialise() == other
        return NotImplemented

    __hash__ = None  # mutable mapping semantics, like dict

    def __bool__(self):
        return True  # a match result is always a non-empty mapping

    def __repr__(self):
        return repr(self._materialise())


def Configure(conf) -> None:
    """Load matcher configuration from a JSON file path or a dict.

    Recognised keys (all optional): ``graph`` (path to a RoadNetwork .npz),
    and any MatchParams field under ``matcher`` (sigma_z, beta, ...).
    """
    global _global_config
    if isinstance(conf, str):
        with open(conf) as f:
            _global_config = json.load(f)
    else:
        _global_config = dict(conf)


class SegmentMatcher:
    """Batched HMM matcher bound to one road network.

    One instance serves the whole process (the reference instead creates
    one C++ matcher per service thread, reporter_service.py:51-58). The
    service serialises device work through its BatchDispatcher thread;
    direct concurrent Match() calls are safe under CPython's GIL (the
    shared RouteCache may redundantly recompute but never corrupts).
    """

    def __init__(self, net: Optional[RoadNetwork] = None,
                 params: Optional[MatchParams] = None,
                 # ~1.5x the default 50 m search radius: reach stays 1 (a
                 # 3x3 cell scan) while each cell holds few edges — 2.5x
                 # faster candidate lookup than the old 250 m cells, with
                 # identical results (the grid is a pure index)
                 grid_cell_m: float = 75.0,
                 use_native: Optional[bool] = None):
        if net is None:
            graph_path = _global_config.get("graph")
            if graph_path is None:
                raise ValueError(
                    "no network: pass net= or Configure({'graph': path})")
            net = RoadNetwork.load(graph_path)
        self.net = net
        if params is None:
            params = MatchParams(**_global_config.get("matcher", {}))
        self.params = params
        self._grid_cell_m = grid_cell_m
        # the numpy structures are only built if the fallback path is used
        # (the native runtime owns its own grid and cache). Lazy-built
        # under a lock: with the circuit breaker, concurrent native-path
        # callers can reach the fallback simultaneously, and a bare
        # check-then-set would race duplicate SpatialGrid/RouteCache
        # builds (losing one copy's cache warmth exactly when degraded)
        self._grid: Optional[SpatialGrid] = None
        self._route_cache: Optional[RouteCache] = None
        self._fallback_lock = _locks.new_lock("matcher.fallback")
        # C++ host runtime when available (and not explicitly disabled);
        # numpy fallback otherwise — identical contract. The
        # REPORTER_TPU_NATIVE knob is the matcher.circuit kill switch:
        # "off" forces the numpy prep leg without rebuilding the server
        # (explicit use_native=True still wins — tests ask by hand).
        self.runtime = None
        if use_native is None and _native_disabled():
            use_native = False
        if use_native is not False:
            from .. import native
            if native.available():
                self.runtime = native.NativeRuntime(net, cell_m=grid_cell_m)
            elif use_native:
                raise RuntimeError("native host runtime requested but "
                                   "unavailable")
        # failure domains, one breaker per hot-path stage (shared
        # threshold/cooldown knobs):
        #   circuit           native prep -> numpy prep fallback
        #   circuit_decode    device decode -> per-trace numpy oracle
        #                     (cpu_ref.viterbi_decode_numpy)
        #   circuit_assemble  native batched assembly -> per-trace scalar
        #                     assembly with poisoned-trace quarantine
        #   circuit_route     device route kernel -> native re-prep with
        #                     host routes (batchpad.prepare_batch)
        #   circuit_incremental  carried-state incremental decode ->
        #                     whole-window batch re-decode (match_many)
        # Fallback outputs are pinned byte-identical (tests/
        # test_report_writer.py, TestDecodeDomain); a half-open probe
        # after the cooldown feels out recovery. The breakers exist even
        # without a runtime/device (they just never trip) so /health can
        # always report every domain's state.
        threshold, cooldown = _circuit_knobs()
        self.circuit = CircuitBreaker("matcher.circuit",
                                      threshold=threshold,
                                      cooldown_s=cooldown)
        self.circuit_decode = CircuitBreaker("matcher.circuit.decode",
                                             threshold=threshold,
                                             cooldown_s=cooldown)
        # assemble's breaker guards quarantine/shedding of poisoned
        # traces inside ONE implementation — there is no dual path to
        # pair, so no FALLBACK_PAIRS entry
        self.circuit_assemble = CircuitBreaker("matcher.circuit.assemble",  # lint: ignore[FB001]
                                               threshold=threshold,
                                               cooldown_s=cooldown)
        self.circuit_route = CircuitBreaker("matcher.circuit.route",
                                            threshold=threshold,
                                            cooldown_s=cooldown)
        self.circuit_incremental = CircuitBreaker(
            "matcher.circuit.incremental",
            threshold=threshold, cooldown_s=cooldown)
        # carried per-trace decode state for the incremental path
        # (matcher/incremental.py); built lazily — batch-only callers
        # never pay for the table
        self._incremental_table = None
        # device route kernel (REPORTER_TPU_ROUTE_DEVICE): built lazily
        # on the first native dispatch — jax import + column upload are
        # not a cost the numpy-only paths should pay. False = build
        # failed / disabled, None = not attempted yet.
        self._route_kernel = None
        self._route_kernel_tried = False
        # where a poisoned trace's request JSON lands when assembly
        # quarantines it (None -> the worker-registered trace spool via
        # utils.spool, else log-and-drop)
        self.quarantine_spool: Optional[str] = None
        # two single-worker device lanes, each FIFO: the dispatch lane
        # runs decode dispatch + async d2h so the device queue stays fed,
        # the drain lane runs the d2h wait + assembly — so chunk N's
        # decode overlaps both host prep of chunk N+1 (main thread) and
        # assembly of chunk N-1 (drain lane). Constructed here (worker
        # threads only spawn on first submit; GC of the matcher releases
        # them) so concurrent first calls can't race a lazy check-then-set
        # into duplicate lanes.
        # (each lane named for the profiler's host lines too, or it
        # would carry the name of the thread that first submitted)
        self._dispatch_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="device-dispatch",
            initializer=metrics.name_os_thread,
            initargs=("device-dispatch",))
        self._drain_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="device-drain",
            initializer=metrics.name_os_thread, initargs=("device-drain",))
        # build the process-global decode mesh NOW (not on the first
        # request): device enumeration + the sharded jit wrappers are
        # one-time costs that belong at init, and a mis-sliced
        # REPORTER_TPU_DEVICE_SLICE should fail loudly here. None when
        # sharding is off or only one local device is visible
        # (REPORTER_TPU_DECODE_SHARD, default auto).
        from ..parallel import mesh as _pmesh
        self.decode_mesh = _pmesh.decode_mesh()

    @property
    def grid(self) -> SpatialGrid:
        if self._grid is None:
            with self._fallback_lock:
                if self._grid is None:
                    self._grid = SpatialGrid(self.net,
                                             cell_m=self._grid_cell_m)
        return self._grid

    @property
    def route_cache(self) -> RouteCache:
        if self._route_cache is None:
            with self._fallback_lock:
                if self._route_cache is None:
                    self._route_cache = RouteCache(self.net)
        return self._route_cache

    @property
    def incremental_table(self):
        """The carried per-trace decode state table (built on first use)."""
        if self._incremental_table is None:
            with self._fallback_lock:
                if self._incremental_table is None:
                    from .incremental import IncrementalTable
                    self._incremental_table = IncrementalTable(self)
        return self._incremental_table

    # -- failure-domain surface --------------------------------------------
    #: domain name -> breaker attribute; the /health "degraded" block,
    #: the worker heartbeat and the chaos assertions all read this map
    CIRCUIT_DOMAINS = (("native.prep", "circuit"),
                       ("decode.dispatch", "circuit_decode"),
                       ("matcher.assemble", "circuit_assemble"),
                       ("route.device", "circuit_route"),
                       ("match.incremental", "circuit_incremental"))

    def _device_route_kernel(self):
        """The lazily-built device route kernel, or None when disabled,
        unavailable, or its one-time build failed (logged once; the host
        route path then serves every chunk)."""
        if not self._route_kernel_tried:
            self._route_kernel_tried = True
            if _route_device_enabled() and self.runtime is not None:
                try:
                    from ..graph.route_device import DeviceRouteKernel
                    self._route_kernel = DeviceRouteKernel(self.net)
                except Exception as e:
                    metrics.count("route.device.build_errors")
                    logger.warning(
                        "REPORTER_TPU_ROUTE_DEVICE is set but the device "
                        "route kernel failed to build (%s); host routes "
                        "serve every chunk", e)
                    self._route_kernel = None
        return self._route_kernel

    def circuit_snapshots(self) -> dict:
        """{domain: breaker snapshot} for every guarded hot-path stage."""
        return {domain: getattr(self, attr).snapshot()
                for domain, attr in self.CIRCUIT_DOMAINS}

    def open_domains(self) -> List[str]:
        """Domains currently open (serving degraded) — [] when healthy."""
        return [domain for domain, attr in self.CIRCUIT_DOMAINS
                if getattr(self, attr).snapshot()["state"] == "open"]

    # -- single-trace, reference-shaped API --------------------------------
    def Match(self, trace_json: str) -> str:
        trace = json.loads(trace_json)
        result = self.match_many([trace])[0]
        if isinstance(result, MatchRuns):
            # columnar writer: JSON bytes straight from the run columns,
            # byte-identical to json.dumps of the materialised dict
            return render_segments_json(result.cols, result.lo, result.hi,
                                        result.mode)
        return json.dumps(result, separators=(",", ":"))

    # -- batched hot path --------------------------------------------------
    def prepare(self, points: Sequence[dict],
                params: Optional[MatchParams] = None):
        """Host prep (candidates + route tensors) for one trace — the
        single owner of the native-vs-numpy dispatch; bench and tests use
        this instead of re-implementing the branch."""
        params = params if params is not None else self.params
        if self.runtime is not None:
            return prepare_trace(self.net, None, points, params,
                                 runtime=self.runtime)
        return prepare_trace(self.net, self.grid, points, params,
                             self.route_cache)

    def match_many(self, traces) -> List[dict]:
        """Match a batch of traces; returns match dicts in order.

        ``traces`` is either a columnar :class:`TraceBatch` (the zero-dict
        hot path — the service, streaming worker, pipeline and bench all
        ingest straight into one) or a sequence of request dicts
        ({"uuid", "trace": [{lat, lon, time, ...}], "match_options"}),
        converted to columns once at this edge. Per-trace match_options
        may override params (reference: generate_test_trace.py:45-52); a
        TraceBatch with one shared options dict resolves params once for
        the whole batch.

        Chunked dispatch pipeline: the main thread runs host prep (one
        native call per chunk when the C++ runtime is present — zero
        per-trace Python) and hands each prepared chunk to two
        single-worker FIFO lanes: the dispatch lane runs decode dispatch
        + async d2h (so the device queue stays fed and h2d transfers
        stream off the main thread), the drain
        lane runs the d2h wait + assembly. Chunk N's decode therefore
        overlaps prep of chunk N+1 AND assembly of chunk N-1.
        REPORTER_TPU_PIPELINE=0 runs both stages inline for a serialized
        per-stage breakdown.
        """
        tb = as_trace_batch(traces)
        ntr = len(tb)
        opts = tb.options
        if opts is None:
            per_trace_params = [self.params] * ntr
        elif isinstance(opts, dict):
            per_trace_params = [self.params.with_options(opts)] * ntr
        else:
            per_trace_params = [
                self.params.with_options(o) if o else self.params
                for o in opts]

        # deferred: importing at module level would cycle through
        # ops -> pallas_viterbi -> matcher.hmm -> matcher/__init__
        from ..ops import batch_pad_multiple, decode_batch

        chunk = _decode_chunk()
        # pad the batch dim to the mesh's data-axis size so decode_batch
        # takes the sharded multi-device path (filler rows are all-SKIP
        # traces that decode to nothing)
        pad = batch_pad_multiple()
        if pad:
            chunk = ((chunk + pad - 1) // pad) * pad

        results: List[Optional[dict]] = [None] * ntr
        futures = []
        if pipeline_enabled():
            def submit(batch, order, sigma, beta):
                # the device lanes run on their own threads: carry the
                # chunk's trace context over the hop so decode/assemble
                # spans parent to the chunk (None when disarmed)
                ctx = obs_trace.current()
                d_fut = self._dispatch_pool.submit(
                    self._lane_stage, ctx, self._dispatch_stage, batch,
                    sigma, beta, decode_batch)
                futures.append((d_fut, self._drain_pool.submit(
                    self._lane_stage, ctx, self._drain_stage, batch,
                    order, d_fut, per_trace_params, results, tb)))
        else:
            def submit(batch, order, sigma, beta):
                decoded = self._dispatch_stage(batch, sigma, beta,
                                               decode_batch)
                self._drain_stage(batch, order, decoded,
                                  per_trace_params, results, tb)

        try:
            if self.runtime is not None:
                self._dispatch_native(tb, per_trace_params, chunk, pad,
                                      submit)
            else:
                self._dispatch_fallback(tb, per_trace_params, chunk,
                                        pad, submit)
        except BaseException:
            # a prep-phase failure must quiesce the lanes before it
            # propagates: later chunks must not keep decoding discarded
            # work into the next call (shared FIFO lanes, shared timers).
            # Two passes: cancel EVERYTHING still queued first (waiting
            # pair-by-pair would let the single-worker lanes dequeue and
            # run later chunks to completion), then wait out whatever had
            # already started.
            running = [f for pair in futures for f in reversed(pair)
                       if not f.cancel()]
            for f in running:
                try:
                    f.result()
                except BaseException:
                    pass
            raise
        # drain EVERY chunk, then surface the first failure in
        # submission order (matches the inline path's raise point); a
        # dispatch-lane error re-raises out of its drain future, so the
        # drain futures cover both lanes
        first_err = None
        for _d_fut, a_fut in futures:
            try:
                a_fut.result()
            except BaseException as e:
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        return results

    def match_incremental(self, traces) -> List[Optional[dict]]:
        """Match via carried per-trace decode state where possible.

        Same input contract as :meth:`match_many`, but each trace with a
        uuid advances its carried decode state by the points appended
        since its last report — O(K) device work per appended point
        instead of a whole-window re-decode. Returns match dicts in
        order with ``None`` for every trace the incremental path did not
        serve (no uuid, kill switch/pressure shed, open circuit, parity
        fallback, eviction, error) — callers route those through
        :meth:`match_many`, whose output is byte-identical by
        construction (tests/test_incremental.py pins this).
        """
        from . import incremental as _inc
        tb = as_trace_batch(traces)
        ntr = len(tb)
        results: List[Optional[dict]] = [None] * ntr
        if ntr == 0:
            return results
        if not _inc.incremental_enabled() or _inc.pressure_shed():
            if self._incremental_table is not None:
                self._incremental_table.clear()
            return results
        if not self.circuit_incremental.allow():
            metrics.count("match.incremental.circuit_skips")
            return results
        opts = tb.options
        if opts is None:
            per_trace_params = [self.params] * ntr
        elif isinstance(opts, dict):
            per_trace_params = [self.params.with_options(opts)] * ntr
        else:
            per_trace_params = [
                self.params.with_options(o) if o else self.params
                for o in opts]
        try:
            with metrics.timer("match.incremental.advance"):
                failures = self.incremental_table.match_many(
                    tb, per_trace_params, results)
        except Exception as e:
            self.circuit_incremental.record_failure()
            logger.warning("incremental match failed (%s); the batch "
                           "path serves this report", e)
            return [None] * ntr
        if failures:
            self.circuit_incremental.record_failure()
        else:
            self.circuit_incremental.record_success()
        return results

    @staticmethod
    def _lane_stage(ctx, fn, *args):
        """Run one device-lane stage under a captured trace context (the
        executor hop drops the submitter's contextvars)."""
        with obs_trace.attach(ctx):
            return fn(*args)

    def _dispatch_stage(self, batch, sigma, beta, decode_batch):
        """Dispatch lane: decode dispatch + async d2h for one chunk.
        Returns the in-flight device array without waiting on it, so the
        next chunk's dispatch isn't gated on this one's results. The
        profiler span attributes any XLA compile this dispatch pays to
        the chunk's (B, T, K) shape — the compile-telemetry tap.

        Failure domain: a dispatch that raises (device lost, compile
        failure, injected ``decode.dispatch`` fault) degrades THAT chunk
        to the per-trace numpy oracle and counts a ``circuit_decode``
        failure; enough consecutive failures open the circuit and later
        chunks skip the device entirely until a half-open probe
        succeeds — the decode twin of the native-prep breaker."""
        B, T, K = batch.dist_m.shape
        with metrics.timer("matcher.decode_dispatch"):
            # deferred device routes (prepare_batch defer_routes): sync
            # the in-flight tensor + settle the wire dtype HERE, on the
            # decode lane, so the prep stage stayed dispatch-only. Every
            # consumer below (device decode, numpy oracle, pressure
            # ladder) reads the finalised tensors. Outside the profiler
            # span: the route pad/cast it runs compiles per ROUTE shape,
            # which must not read as a decode-shape recompile
            batch.finalize_wire()
            with profiler.dispatch_span(B, T, K, str(batch.dist_m.dtype)):
                if _pressure_oracle:
                    # the ladder's last rung: identical results (the
                    # oracle is the breaker's fallback, bit-identical on
                    # scan), device left to the recovery drain
                    metrics.count("pressure.oracle_chunks")
                    return self._decode_numpy_chunk(batch, sigma, beta)
                if not self.circuit_decode.allow():
                    metrics.count(
                        "matcher.circuit.decode.fallback_chunks")
                    return self._decode_numpy_chunk(batch, sigma, beta)
                try:
                    faults.failpoint("decode.dispatch")
                    decoded, _scores = decode_batch(
                        batch.dist_m, batch.valid, batch.route_m,
                        batch.gc_m, batch.case, sigma, beta)
                    if hasattr(decoded, "copy_to_host_async"):
                        decoded.copy_to_host_async()
                except Exception as e:
                    self.circuit_decode.record_failure()
                    metrics.count("matcher.circuit.decode.errors")
                    logger.warning(
                        "device decode failed for a (%d, %d, %d) chunk "
                        "(%s); decoding it via the numpy oracle", B, T, K,
                        e)
                    return self._decode_numpy_chunk(batch, sigma, beta)
                self.circuit_decode.record_success()
        return decoded

    def _decode_numpy_chunk(self, batch, sigma, beta) -> np.ndarray:
        """Degraded decode: the per-trace numpy Viterbi oracle
        (cpu_ref.viterbi_decode_numpy — the same implementation the
        shadow-accuracy sampler scores the device against) over every
        row of the chunk. Consumes the SAME prepared tensors as the
        device kernels, so on the scan backend (the single-device CPU
        default) the paths — and therefore the report bytes — are
        bit-identical (pinned by TestDecodeDomain); tie-breaks may
        differ only vs the associative-scan backend, where equal-score
        paths already diverge between device backends."""
        from .cpu_ref import viterbi_decode_numpy
        dist = np.asarray(batch.dist_m, dtype=np.float32)
        valid = np.asarray(batch.valid)
        T = dist.shape[1]
        # the native prep path carries a dead trailing time row (T rows,
        # for seq sharding); the oracle wants the documented T-1
        route = np.asarray(batch.route_m[:, :max(T - 1, 0)],
                           dtype=np.float32)
        gc = np.asarray(batch.gc_m[:, :max(T - 1, 0)], dtype=np.float32)
        case = np.asarray(batch.case)
        # rows past len(batch.traces) are all-SKIP pow2/mesh padding the
        # device batch carries; assembly never reads them (decoded[:B]),
        # so the oracle must not pay a full Viterbi per filler row —
        # degraded mode is exactly when throughput is scarcest
        out = np.zeros(dist.shape[:2], dtype=np.int32)
        for b in range(len(batch.traces)):
            out[b], _score = viterbi_decode_numpy(
                dist[b], valid[b], route[b], gc[b], case[b], sigma, beta)
        return out

    def _drain_stage(self, batch, order, decoded, per_trace_params,
                     results, tb=None) -> None:
        """Drain lane: d2h wait + assembly + result formatting for one
        chunk. ``decoded`` is the dispatch stage's device array, or a
        Future of it on the pipelined path; writes into ``results`` slots
        owned exclusively by this chunk's ``order``. ``tb`` is the call's
        TraceBatch — the source the poisoned-trace quarantine rebuilds a
        replayable request body from."""
        if hasattr(decoded, "result"):
            decoded = decoded.result()
        with metrics.timer("matcher.decode_wait"):
            decoded = np.asarray(decoded)
        # shadow-accuracy tap: maybe re-decode this chunk through the
        # numpy oracle on the profiler's background thread (sampled,
        # REPORTER_TPU_SHADOW_SAMPLE; one flag-cheap call when off)
        p0 = per_trace_params[order[0]]
        profiler.maybe_shadow(batch, decoded, len(order),
                              p0.effective_sigma, p0.beta)
        if batch.prep is not None:
            # native batched assembly: ONE call walks every decoded
            # path of this batch into run records; the results are lazy
            # MatchRuns views over ONE shared RunColumns — no per-run
            # dicts here, the serving path serialises straight from the
            # columns (render_segments_json / service report_json).
            # Failure domain: one poisoned trace used to fail the WHOLE
            # chunk here; now a failed batch call counts a
            # ``circuit_assemble`` failure and the chunk degrades to the
            # per-trace scalar assembler below, which isolates the
            # poison to its own trace.
            if self.circuit_assemble.allow():
                B = len(batch.traces)
                gp = per_trace_params[order[0]]
                try:
                    with metrics.timer("matcher.assemble"):
                        faults.failpoint("matcher.assemble")
                        runs = self.runtime.assemble_batch(
                            decoded[:B], batch.prep, batch.pt_off,
                            batch.times_flat,
                            queue_threshold_kph=gp.queue_speed_threshold_kph,
                            interpolation_distance_m=gp.interpolation_distance,
                            backward_tolerance_m=gp.backward_tolerance_m,
                            turn_penalty_factor=gp.turn_penalty_factor)
                        ro = runs["run_off"].tolist()
                        cols = RunColumns(runs)
                        # chunk wire layout for the batch writer
                        # (native.write_report_json_batch): per-trace
                        # run spans + last point times, so the FIRST
                        # /report serialisation of this chunk can emit
                        # every trace's body in one C call and the
                        # rest slice it (service/wire.py memo)
                        pt_off = np.ascontiguousarray(batch.pt_off,
                                                      dtype=np.int64)
                        cols.arrays["_run_off"] = np.ascontiguousarray(
                            runs["run_off"], dtype=np.int64)
                        cols.arrays["_trace_end"] = np.ascontiguousarray(
                            np.asarray(batch.times_flat,
                                       dtype=np.float64)[pt_off[1:] - 1])
                        for b, i in enumerate(order):
                            results[i] = MatchRuns(
                                cols, ro[b], ro[b + 1],
                                per_trace_params[i].mode)
                except Exception as e:
                    self.circuit_assemble.record_failure()
                    metrics.count("matcher.circuit.assemble.native_errors")
                    logger.warning(
                        "batched assembly failed for a %d-trace chunk "
                        "(%s); assembling it per trace", len(order), e)
                else:
                    self.circuit_assemble.record_success()
                    return
            else:
                metrics.count("matcher.circuit.assemble.fallback_chunks")
        # per-trace scalar assembly — the numpy-path default AND the
        # assemble-domain degraded mode: each trace assembles in its own
        # try, so a poisoned trace quarantines alone instead of failing
        # the chunk. order is elementwise-aligned with batch.traces (the
        # dispatchers build it that way), so row b IS trace order[b].
        with metrics.timer("matcher.assemble"):
            for b, i in enumerate(order):
                params = per_trace_params[i]
                try:
                    faults.failpoint("matcher.assemble")
                    results[i] = assemble_segments(
                        self.net, batch.traces[b], decoded[b],
                        mode=params.mode,
                        queue_threshold_kph=params.queue_speed_threshold_kph,
                        interpolation_distance_m=params.interpolation_distance,
                        backward_tolerance_m=params.backward_tolerance_m,
                        turn_penalty_factor=params.turn_penalty_factor)
                except Exception as e:
                    self._quarantine_trace(tb, int(i), e)
                    # the caller still gets a well-formed (empty) match
                    # for the poisoned slot; every other trace's bytes
                    # are unchanged (pinned by TestAssembleDomain).
                    # Dict-per-poisoned-trace is the cold quarantine
                    # path, not the per-trace steady state.
                    results[i] = {"segments": [],  # lint: ignore[HP002]
                                  "mode": params.mode}

    def _quarantine_trace(self, tb, i: int, err: Exception) -> None:
        """Spool a poisoned trace's request JSON (/report-ready — the
        dead-letter replayer re-submits it verbatim) to the trace
        dead-letter spool; best-effort, counted either way."""
        metrics.count("matcher.assemble.quarantined")
        from ..utils import spool
        root = self.quarantine_spool or spool.trace_dir()
        uuid = tb.uuid(i) if tb is not None else None
        if root is None or tb is None:
            logger.error("quarantined poisoned trace %s (%s) with no "
                         "dead-letter spool configured", uuid, err)
            return
        try:
            body = tb[i].to_request()
            # deterministic per-uuid name: when the dead-letter REPLAY
            # of this body poisons again, the re-quarantine overwrites
            # this entry instead of minting a fresh one — the drainer's
            # shared uuid budget can then converge it to .quarantine
            # rather than chase an ever-growing family of copies
            name = f"poison.{uuid or 'anon'}.json"
            path = spool.write(root, name,
                               json.dumps(body, separators=(",", ":")))
            logger.warning("quarantined poisoned trace %s -> %s (%s)",
                           uuid, path, err)
        except Exception as spool_err:  # never fail the chunk for this
            logger.error("poisoned-trace quarantine failed for %s: %s "
                         "(original error: %s)", uuid, spool_err, err)

    # every param that shapes the prepared tensors or the batched
    # assembly: traces may only share one native prep call (and one device
    # batch) when all of these agree; sigma/beta ride along because they
    # are batch-wide scalars on device
    _PREP_KEY_FIELDS = (
        "effective_sigma", "beta", "max_candidates", "search_radius",
        "interpolation_distance", "breakage_distance",
        "max_route_distance_factor", "backward_tolerance_m",
        "max_route_time_factor", "min_time_bound_s", "turn_penalty_factor",
        "queue_speed_threshold_kph")

    def _param_groups(self, per_trace_params):
        """[(params, index array)] — one group per distinct prep-param
        key, insertion-ordered. The steady state (one shared options
        dict, so one params object for the whole batch) is an identity
        scan, no per-trace key tuples."""
        ntr = len(per_trace_params)
        if ntr == 0:
            return []
        p0 = per_trace_params[0]
        if all(p is p0 for p in per_trace_params):
            return [(p0, np.arange(ntr, dtype=np.int64))]
        keyed: dict[tuple, tuple] = {}
        for i, p in enumerate(per_trace_params):
            key = tuple(getattr(p, f) for f in self._PREP_KEY_FIELDS)
            got = keyed.get(key)
            if got is None:
                keyed[key] = (p, [i])
            else:
                got[1].append(i)
        return [(p, np.asarray(idxs, dtype=np.int64))
                for p, idxs in keyed.values()]

    def _dispatch_native(self, tb: TraceBatch, per_trace_params, chunk,
                         pad, submit):
        """Hot path: group by prep params, plan each group's chunks
        (``_plan_chunks``), then ONE rt_prepare_batch call per chunk on
        this thread — the chunk's flat coordinate columns pass straight
        from the TraceBatch to the native call, zero per-point Python —
        handing each prepared batch to ``submit`` (the device lanes).

        Failure domain: each chunk consults the circuit breaker. A
        native prep error degrades THAT chunk to the numpy path (the
        caller still gets every result) and counts a breaker failure;
        enough consecutive failures open the circuit and subsequent
        chunks skip native entirely until a half-open probe succeeds.
        """
        workers = max(1, _prep_workers())
        raw_counts = np.diff(tb.offsets)  # per-trace raw point counts
        ci = 0  # chunk index across the whole call, a span attribute
        for params, idxs in self._param_groups(per_trace_params):
            sigma = np.float32(params.effective_sigma)
            beta = np.float32(params.beta)
            for T, part, coalesced in self._plan_chunks(idxs, raw_counts,
                                                        pad, chunk):
                # part itself is the order: _drain_stage only
                # enumerates it, so no per-chunk list conversion
                # (reporter-lint HP003)
                order = part
                rows = padded_batch_rows(len(part), pad)
                metrics.count("decode.chunks")
                with obs_trace.span("matcher.chunk", chunk=ci,
                                    traces=len(part), T=int(T)):
                    ci += 1
                    if not self.circuit.allow():
                        metrics.count("matcher.circuit.fallback_chunks")
                        self._submit_numpy_chunk(tb, part, params, pad,
                                                 submit, sigma, beta)
                        continue
                    try:
                        with metrics.timer("matcher.prep"):
                            faults.failpoint("native.prep")
                            batch = prepare_batch(
                                self.runtime, tb.gather(part), params,
                                int(T), pad_rows=rows, n_threads=workers,
                                route_kernel=self._device_route_kernel(),
                                route_circuit=self.circuit_route,
                                # device-resident route tensor: the
                                # decode stage pays the sync
                                # (finalize_wire), overlapped with the
                                # next chunk's prep
                                defer_routes=True)
                    except Exception as e:
                        self.circuit.record_failure()
                        metrics.count("matcher.circuit.native_errors")
                        logger.warning(
                            "native prep failed for a %d-trace chunk "
                            "(%s); serving it via the numpy fallback",
                            len(part), e)
                        self._submit_numpy_chunk(tb, part, params, pad,
                                                 submit, sigma, beta)
                        continue
                    self.circuit.record_success()
                    # the chunk's wide event: occupancy vs the padded
                    # (rows, T) grid, memo state, queue depth — one
                    # call per CHUNK, not per trace
                    profiler.chunk_event(
                        bucket_T=int(T), K=params.max_candidates,
                        traces=len(part), rows=int(batch.case.shape[0]),
                        kept_points=kept_point_count(batch),
                        raw_points=int(raw_counts[part].sum()),
                        cache=self.runtime.route_memo_stats(),
                        path="native", coalesced=coalesced)
                    submit(batch, order, sigma, beta)

    @staticmethod
    def _plan_chunks(group, raw_counts, pad=None, chunk=None):
        """The chunk plan for one params group: ``[(T, index array,
        coalesced)]``, one entry per decode chunk, i.e. per prep call,
        decode dispatch and assembly the group pays.

        The per-bucket plan groups traces by ladder bucket of their RAW
        length (kept length is only known after the native prep; raw is
        an upper bound, so a jitter-heavy trace may decode in a larger
        bucket — same decoded path, the SKIP tail is inert), lets
        ``_split_bucket`` break a wasteful bucket into pow2 sub-buckets,
        and cuts each (sub-)bucket into ``chunk``-row chunks.

        Then merge: a group of at most ``PIPELINED_CHUNK`` traces (and
        at most ``chunk``), which the per-bucket plan cuts into more
        than one chunk, decodes as ONE chunk instead, at the smallest
        power of two holding its longest raw trace (clipped to [ladder
        floor, that trace's ladder bucket]; the bucket itself while
        splitting is off, so the pressure ladder's coarse rung forms no
        new shape) — but only where the merge's extra padded cells stay
        within ``CHUNK_COST_CELLS`` for each chunk it saves, so one long
        trace never pads a micro-batch of short ones to its length
        (``decode.bucket.coalesced``). The cap is the one-device
        pipelined chunk whatever the mesh or the pipeline mode scale
        ``chunk`` to: a larger group (a streaming flush of more than
        128 traces, a batch-pipeline or mesh chunk) keeps the per-bucket
        plan and the lanes' overlap across its chunks. A merged chunk
        is marked ``coalesced``: its padding is chosen, so it stays out
        of the per-T waste the splitter consults."""
        ladder, thresh = bucket_ladder()
        buckets = np.asarray(ladder, dtype=np.int64)
        raws = raw_counts[group]
        Ts = buckets[np.minimum(
            np.searchsorted(buckets, np.maximum(raws, 1)),
            len(buckets) - 1)]
        plan, splits = [], 0
        for T0 in np.unique(Ts).tolist():
            sub = SegmentMatcher._split_bucket(T0, group[Ts == T0],
                                               raw_counts, pad, chunk)
            splits += len(sub) > 1 or sub[0][0] != T0
            plan += sub
        step = chunk or len(group)
        if len(plan) > 1 and len(group) <= min(step, PIPELINED_CHUNK):
            top = int(Ts.max())
            T = top if thresh >= 1.0 else min(
                max(_next_pow2(int(raws.max())), int(ladder[0])), top)
            # every (sub-)bucket of a group this small is one chunk
            planned = sum(SegmentMatcher._padded_cells(len(b), pad, t,
                                                       step)
                          for t, b in plan)
            merged = SegmentMatcher._padded_cells(len(group), pad, T, step)
            if merged - planned <= CHUNK_COST_CELLS * (len(plan) - 1):
                metrics.count("decode.bucket.coalesced")
                return [(T, group, True)]
        if splits:
            metrics.count("decode.bucket.split", splits)
        return [(T, bucket[lo:lo + step], False) for T, bucket in plan
                for lo in range(0, len(bucket), step)]

    @staticmethod
    def _padded_cells(n: int, pad, T: int, chunk) -> int:
        """Point cells ``n`` traces of bucket ``T`` actually decode as,
        chunked exactly as the dispatch loop chunks them — each chunk
        re-pays its own mesh-multiple + pow2 row padding."""
        cells = 0
        while n > 0:
            take = min(n, chunk) if chunk else n
            cells += padded_batch_rows(take, pad) * T
            n -= take
        return cells

    @staticmethod
    def _split_bucket(T: int, group, raw_counts, pad=None, chunk=None):
        """The occupancy-driven adaptive splitter, the per-bucket plan's
        second step: ``[(sub_T, index array)]`` for one ladder-bucket
        group, ``[(T, group)]`` when no split pays (``_plan_chunks``
        counts ``decode.bucket.split`` for each split it keeps; a small
        group it merges into one chunk is not split). A split breaks
        a mixed-length group into per-pow2-bucket sub-batches
        (per-trace smallest power of two >= raw
        length, clipped to [ladder floor, T]) when the padding waste of
        decoding everything at T exceeds the ladder's threshold —
        consulting the RECORDED per-bucket waste from PR 8's wide
        events (profiler.bucket_waste) once chunks of this T have been
        measured, and a projection from this group's raw lengths before
        that (kept <= raw, so the projection under-states waste and
        never over-splits). ``pad`` is the mesh row multiple: a split
        only happens when the total padded point cells ACROSS the
        sub-batches — each re-paying mesh-multiple + pow2 ROW padding —
        actually drop, so splitting can never trade tail pad for worse
        filler-row pad (a 4-trace sub-batch on an 8-wide mesh pads
        right back to 8 rows). Decoded paths are unchanged — the SKIP
        tail is inert, pinned byte-identical by
        tests/test_sharded_decode.py — and the shape cost is bounded:
        sub-buckets are powers of two, each new (rows, T) pair is ONE
        compile episode, and a second compile of the same shape still
        trips the storm counter."""
        ladder, thresh = bucket_ladder()
        if thresh >= 1.0 or len(group) < 2 or T <= int(ladder[0]):
            return [(T, group)]
        raws = np.minimum(raw_counts[group], T)
        # decision waste = max(projected, recorded). The projection
        # uses the same denominator the recorded waste does — PADDED
        # rows chunked exactly as dispatch will chunk them (mesh
        # multiple + pow2 filler counts as waste there too) — with
        # kept <= raw in the numerator, so it under-states and never
        # over-splits; the recorded per-bucket number (PR 8's wide
        # events) catches what the projection can't see (kept << raw
        # on jitter-heavy streams). max, not recorded-first: after a
        # split, the low-waste SUB-chunks record under this same T
        # and a recorded-first read would oscillate
        # (split -> record low -> stop splitting -> record high -> ...)
        cells_unsplit = SegmentMatcher._padded_cells(len(group), pad, T,
                                                     chunk)
        waste = 1.0 - float(raws.sum()) / cells_unsplit
        recorded = profiler.bucket_waste(T)
        if recorded is not None:
            waste = max(waste, recorded)
        if waste <= thresh:
            return [(T, group)]
        subTs = np.minimum(np.maximum(
            np.exp2(np.ceil(np.log2(np.maximum(raws, 1))))
            .astype(np.int64), int(ladder[0])), T)
        uniq, counts = np.unique(subTs, return_counts=True)
        if uniq.tolist() == [T]:
            return [(T, group)]
        cells_split = int(sum(
            SegmentMatcher._padded_cells(int(c), pad, int(s), chunk)
            for s, c in zip(uniq.tolist(), counts.tolist())))
        if cells_split >= cells_unsplit:
            return [(T, group)]
        return [(int(s), group[subTs == s]) for s in uniq.tolist()]

    def _submit_numpy_chunk(self, tb: TraceBatch, part, params, pad,
                            submit, sigma, beta) -> None:
        """Prep ONE chunk through the numpy path and hand its packed
        batches to the device lanes — the degraded lane the circuit
        breaker routes native chunks through, and the inner step of
        ``_dispatch_fallback``. Contract identical to native prep
        (results pinned byte-equal by tests/test_report_writer.py)."""
        with metrics.timer("matcher.prep"):
            prepped = prepare_traces_numpy(
                self.net, self.grid, tb.gather(part), params,
                self.route_cache)
        # chunk-granular identity bookkeeping on the numpy fallback
        # path (one small dict per chunk, not per point)
        idx_of = {id(p): i for p, i in zip(prepped, part)}
        for batch in pack_batches(prepped, pad_batch_to=pad,
                                  pad_pow2=True):
            # rows of a packed batch align with its traces list, so
            # order[b] is the global index of batch.traces[b]
            order = [idx_of[id(p)] for p in batch.traces]
            profiler.chunk_event(
                bucket_T=int(batch.case.shape[1]),
                K=params.max_candidates, traces=len(order),
                rows=int(batch.case.shape[0]),
                kept_points=kept_point_count(batch),
                raw_points=int(sum(p.num_raw for p in batch.traces)),
                cache=_route_cache_counters(), path="numpy")
            submit(batch, order, sigma, beta)

    def _dispatch_fallback(self, tb: TraceBatch, per_trace_params, chunk,
                           pad, submit):
        """numpy prep path (no native library): whole-chunk vectorised
        candidate search + per-trace route tensors through the shared
        cross-batch route cache, then pack_batches — same contract as the
        native path, slower."""
        ci = 0
        for params, idxs in self._param_groups(per_trace_params):
            sigma = np.float32(params.effective_sigma)
            beta = np.float32(params.beta)
            for lo in range(0, len(idxs), chunk):
                part = idxs[lo:lo + chunk]
                metrics.count("decode.chunks")
                with obs_trace.span("matcher.chunk", chunk=ci,
                                    traces=len(part)):
                    ci += 1
                    self._submit_numpy_chunk(tb, part, params, pad,
                                             submit, sigma, beta)
