#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from the cell's entry in ``BENCHMARK.json``:
its configuration (``benchmark/configs/<config>.json``), its traffic mix
(``benchmark/traffic/<traffic>.json``), the mix's driver
(``benchmark/drivers/<driver>.py``, whose ``serve`` runs the cell in
this process), the kernels whose calls are counted
(``benchmark/kernels/<kind>.py``) and, with ``--trace 1``, a reader per
per-layer metric (``benchmark/metrics/<metric>.py``).

A run fails, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for, where any fallback counter (``MUST_BE_ZERO``)
moves in the warm-up or the window, or where anything compiles in the
window. The last lines on stderr, and the ``limits`` key that ends the
result line, give each number the correctness check compared beside its
limit.

``--rehearse`` runs the same code on the CPU at a tiny size (each file's
``rehearse`` group), prints everything but never the result line, and
exits 3. ``--control 1`` puts the control (the plain reference one step
down in precision) in the program's place in the check, so that the run
reads not correct. ``--fault`` breaks the timed path, for the tests.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import (CACHE, ROOT, Cell, CompileClock, Failure,  # noqa: E402
                     log, native_build)


def process_start() -> float:
    import psutil
    return psutil.Process().create_time()


def main(argv=None) -> int:
    t_start = process_start()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal: never prints a result line")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="check the control in the program's place: the "
                   "run has to read not correct (for setting limits)")
    p.add_argument("--fault", choices=("answer", "half_batch"),
                   help=argparse.SUPPRESS)  # tests: break the timed path
    args = p.parse_args(argv)

    cell = Cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["REPORTER_TPU_PLATFORM"] = "cpu"
    else:
        # the compile cache at a fixed path in the checkout, so that only
        # a cell's first run there compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    try:
        from reporter_tpu.utils.runtime import ensure_backend
    except ImportError as e:
        raise Failure(f"no program next to the benchmark: {e}")
    try:
        ensure_backend("cpu" if args.rehearse else "tpu")
    except Exception as e:
        raise Failure(f"no TPU: {e}")
    native_s = native_build()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log("device", native_build_s=native_s, **device)
    if not args.rehearse:
        if dev.platform != "tpu":
            raise Failure(f"platform {dev.platform}, not tpu")
        if len(devs) < cell.entry["chips"]:
            raise Failure(f"{len(devs)} chips; the cell needs "
                          f"{cell.entry['chips']}")
    clock = CompileClock()

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(CACHE, "traces", f"{cell.name}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = cell.driver.serve(cell, args, clock, t_start, trace_dir)

    limits, ok = cell.limits(res["check"])
    metrics = {}
    extra = {}
    if not args.trace:
        metrics.update(res["end_to_end"])
        metrics["setup_s"] = {"value": res["setup_s"], "unit": "s"}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    else:
        import devtrace
        from readings import Readings
        t0 = time.perf_counter()
        summary = devtrace.summarise(devtrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        r = Readings(res["counters"], res["timers"], res["calls"], summary,
                     dev.device_kind)
        for name, mod in cell.readers().items():
            v = mod.read(r)
            if v is not None:
                unit = next(m["unit"] for m in cell.per_layer
                            if m["name"] == name)
                metrics[name] = {"value": v, "unit": unit}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        extra["breakdown"] = summary.breakdown()
        log("trace", reduce_s=time.perf_counter() - t0,
            modules=summary.modules, calls=summary.module_calls)
    device["memory_peak_bytes"] = res["memory"]
    line = {"correct": ok, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device,
            **extra, "limits": limits}
    log("check", **res["check"])
    for name, v in limits.items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    if args.rehearse:
        log("rehearsal", line=line)
        print("bench: rehearsal passed; no result line off the chip",
              flush=True)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failure as e:
        print(f"bench FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
