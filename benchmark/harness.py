"""What every cell's run shares, whatever its driver: the cell found by
name, the native build, the compile clock, the program's counters read
as deltas, the kernel spy and the measured window.

A driver's ``serve(cell, args, clock, t_start, trace_dir)`` runs in the
parent, which holds the chip, and returns the run's result:
``setup_s``, ``counters`` and ``timers`` (deltas over the window),
``calls`` (the kernel spy's), ``memory``, ``end_to_end`` (metric ->
{value, unit}), ``check`` (each number compared, by name),
``attempted`` and ``failed``. :class:`Window` does the measuring that
every driver needs.
"""
from __future__ import annotations

import copy
import gc
import hashlib
import importlib
import importlib.util
import json
import os
import socket
import subprocess
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")

#: counters that name a fallback, a breaker failure, a quarantine or a
#: recompile (chip_smoke.py's list): any move fails the run
MUST_BE_ZERO = (
    "matcher.circuit.failures",
    "matcher.circuit.fallback_chunks",
    "matcher.circuit.native_errors",
    "matcher.circuit.decode.failures",
    "matcher.circuit.decode.errors",
    "matcher.circuit.decode.fallback_chunks",
    "matcher.circuit.assemble.failures",
    "matcher.circuit.assemble.native_errors",
    "matcher.circuit.assemble.fallback_chunks",
    "matcher.assemble.quarantined",
    "matcher.circuit.route.failures",
    "route.device.errors",
    "route.device.fallback_chunks",
    "route.device.build_errors",
    "route.device.finalize_errors",
    "route.device.circuit_skipped_chunks",
    "pressure.oracle_chunks",
    "wire.circuit.failures",
    "wire.errors",
    "wire.fallback",
    "matcher.circuit.incremental.failures",
    "match.incremental.errors",
    "match.incremental.circuit_skips",
    "match.incremental.shadow_mismatches",
    "decode.compile.recompiles",
    "decode.shadow.errors",
    "decode.shadow.mismatch",
)


class Failure(Exception):
    """The run cannot give a result."""


def log(what: str, **fields) -> None:
    print(f"bench {what}: " + json.dumps(fields, default=str), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested groups key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


class Cell:
    """A cell's entry with its configuration, traffic mix, driver and the
    metrics it reports, all found by name. A rehearsal lays each file's
    ``rehearse`` group over it: the same code at a size the CPU holds."""

    def __init__(self, name: str, rehearse: bool = False):
        try:
            self.bench = load_json("BENCHMARK.json")
        except FileNotFoundError:
            raise Failure("no BENCHMARK.json at the checkout's root")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise Failure(f"no cell {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        self.rehearse = rehearse
        self.config_name = self.entry["config"]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.config_name]
        self.config = load_json(conf["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json("benchmark", "traffic",
                                 self.traffic_name + ".json")
        if rehearse:
            self.config = merged(self.config, self.config.get("rehearse", {}))
            self.traffic = merged(self.traffic,
                                  self.traffic.get("rehearse", {}))
        self.driver = importlib.import_module(
            f"drivers.{self.traffic['driver']}")
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in self.bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def readers(self) -> dict:
        return {m["name"]: load_module(
            os.path.join(HERE, "metrics", m["name"] + ".py"),
            "metric_" + m["name"].replace(".", "_"))
            for m in self.per_layer}

    def limits(self, check: dict) -> tuple:
        """(each number compared with its limit, whether all hold): the
        configuration's ``check.limits``, then ``unanswered`` at 0."""
        out = {}
        ok = True
        for name, limit in self.config["check"]["limits"].items():
            value = check.get(name)
            out[name] = {"value": value, "limit": limit}
            ok = ok and value is not None and value <= limit
        out["unanswered"] = {"value": check.get("unanswered"), "limit": 0}
        ok = ok and check.get("unanswered") == 0
        return out, ok


# ---------------------------------------------------------------- set-up

def native_build() -> float:
    """Build the host runtime where it is missing or was built from other
    source or on another machine; once per checkout and machine."""
    ndir = os.path.join(ROOT, "reporter_tpu", "native")
    h = hashlib.sha256()
    for name in ("Makefile", os.path.join("src", "host_runtime.cpp")):
        with open(os.path.join(ndir, name), "rb") as f:
            h.update(f.read())
    h.update(socket.gethostname().encode())
    with open("/proc/cpuinfo", "rb") as f:
        h.update(b" ".join(ln for ln in f.read().splitlines()
                           if ln.startswith(b"flags"))[:4096])
    want = h.hexdigest()
    stamp = os.path.join(CACHE, "native.stamp")
    lib = os.path.join(ndir, "libreporter_host.so")
    try:
        with open(stamp) as f:
            have = f.read().strip()
    except FileNotFoundError:
        have = ""
    if have == want and os.path.exists(lib):
        return 0.0
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-C", ndir], check=True,
                   stdout=subprocess.DEVNULL)
    os.makedirs(CACHE, exist_ok=True)
    with open(stamp, "w") as f:
        f.write(want)
    return time.perf_counter() - t0


class CompileClock:
    """Every backend compile in the process, from ``jax.monitoring``
    (chip_smoke.py's clock): the event fires for a persistent-cache hit
    too, so a new executable counts either way."""

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.names = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, dur, fun_name="?", **_kw):
        if name.endswith("backend_compile_duration"):
            with self._lock:
                self.names.append(fun_name)
                self.seconds += dur

    def read(self):
        with self._lock:
            return len(self.names), self.seconds

    def since(self, n: int) -> dict:
        with self._lock:
            names = self.names[n:]
        return {f: names.count(f) for f in sorted(set(names))}


def snapshot() -> tuple:
    from reporter_tpu.utils import metrics
    snap = metrics.default.snapshot()
    return snap["counters"], {k: (v["count"], v["total_s"])
                              for k, v in snap["timers"].items()}


def delta(before: tuple, after: tuple) -> tuple:
    c0, t0 = before
    c1, t1 = after
    counters = {k: v - c0.get(k, 0) for k, v in c1.items()
                if v != c0.get(k, 0)}
    timers = {k: (v[0] - t0.get(k, (0, 0.0))[0],
                  v[1] - t0.get(k, (0, 0.0))[1]) for k, v in t1.items()}
    return counters, timers


def check_zero(what: str, counters: dict) -> None:
    bad = {k: counters[k] for k in MUST_BE_ZERO if counters.get(k)}
    if bad:
        raise Failure(f"{what}: fallback counters moved: {bad}")


def memory_peak() -> int:
    import jax
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.local_devices() if d.memory_stats()]
    return int(max(peaks, default=0))


# ---------------------------------------------------------------- window

class CallSpy:
    """Counts the calls the window makes into each kernel of
    ``benchmark/kernels/`` (``roofline.kernels``), by the shape key its
    file derives from the call: the shapes its roofline's work is
    computed from. Each call also runs in a host span
    ``bench.<kind>_dispatch``. ``fault`` plants a kernel's own ``plant``
    in every call of a kernel that has one (``--fault``, for the tests)."""

    def __init__(self, fault: "str | None" = None):
        from roofline import kernels
        self.calls = {}
        self.on = False
        self.lock = threading.Lock()
        self.saved = []
        for kind, mod in kernels().items():
            owner = importlib.import_module(mod.TARGET[0])
            real = getattr(owner, mod.TARGET[1])
            setattr(owner, mod.TARGET[1],
                    self._wrap(kind, mod, real, fault))
            self.saved.append((owner, mod.TARGET[1], real))

    def _wrap(self, kind: str, mod, real, fault):
        import jax
        span = f"bench.{kind}_dispatch"
        plant = getattr(mod, "plant", None) if fault else None

        def call(*a, **kw):
            if self.on:
                key = (kind, *mod.shape(*a, **kw))
                with self.lock:
                    self.calls[key] = self.calls.get(key, 0) + 1
            with jax.profiler.TraceAnnotation(span):
                out = real(*a, **kw)
            return plant(fault, a, kw, out) if plant else out

        return call

    def close(self) -> None:
        for owner, name, real in reversed(self.saved):
            setattr(owner, name, real)


class Window:
    """The measured window, as every driver needs it: on entry the
    kernel spy counts, the counters and the compile count are read and,
    with a trace directory, the profiler starts; on exit the profiler
    stops, the counters are read again, the device's peak memory is read
    and the collector's full passes in the window are logged. After
    ``finish()`` the window's deltas are in ``counters`` and ``timers``;
    it fails the run where a fallback counter moved since ``start`` (the
    warm-up's snapshot) or anything compiled in the window."""

    def __init__(self, clock: CompileClock, trace_dir, fault=None):
        self.clock = clock
        self.trace_dir = trace_dir
        self.spy = CallSpy(fault)
        self.pauses = []
        self._gc_t0 = 0.0

    def _gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._gc_t0)

    def __enter__(self):
        self.c1, _s = self.clock.read()
        self.before = snapshot()
        gc.callbacks.append(self._gc)
        self.spy.on = True
        if self.trace_dir:
            import jax
            # the python tracer off: it slowed the host 2.7-fold (my chip
            # run, PR 22); the layer spans name the gaps instead
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t_window = time.time()
        return self

    def __exit__(self, *exc) -> None:
        try:
            if self.trace_dir:
                import jax
                jax.profiler.stop_trace()
        finally:
            self.spy.on = False
            self.spy.close()
            gc.callbacks.remove(self._gc)
        self.after = snapshot()
        self.c2, _s = self.clock.read()
        self.memory = memory_peak()

    def finish(self, start: tuple, **fields) -> None:
        self.counters, self.timers = delta(self.before, self.after)
        log("window", compiles_in_window=self.c2 - self.c1,
            compiled=self.clock.since(self.c1),
            kernel_calls=sum(self.spy.calls.values()),
            gc_full_passes=len(self.pauses),
            gc_full_pass_max_ms=1e3 * max(self.pauses, default=0.0),
            route_cache={k: v for k, v in self.counters.items()
                         if k.startswith("route.cache")}, **fields)
        check_zero("window", delta(start, self.after)[0])
        if self.c2 != self.c1:
            raise Failure(f"{self.c2 - self.c1} compiles in the window: "
                          f"{self.clock.since(self.c1)}")

    def result(self, t_start: float, **res) -> dict:
        return {"setup_s": self.t_window - t_start,
                "counters": self.counters, "timers": self.timers,
                "calls": dict(self.spy.calls), "memory": self.memory,
                **res}
