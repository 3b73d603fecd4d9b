"""A kernel's least time at the chip's peaks, from the work its calls
need.

Each kernel the benchmark counts is a file of its own,
``benchmark/kernels/<kind>.py``, found by :func:`kernels`: its
``TARGET`` (the program's function the spy wraps), ``shape`` (a call's
shape key), ``MODULES`` (its device modules in the trace) and ``work``
(operations and bytes the call needs, whichever backend runs it). So a
share of a roofline can only pass 100% if the device time leaves out
part of the work.
"""
from __future__ import annotations

import glob
import os

from peaks import peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def kernels() -> dict:
    """kind -> the module of ``kernels/<kind>.py``, every one there."""
    from harness import load_module
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "kernels", "*.py"))):
        kind = os.path.basename(path)[:-len(".py")]
        out[kind] = load_module(path, "kernel_" + kind)
    return out


def min_seconds(ops: float, nbytes: float, device_kind: str) -> tuple:
    """(least seconds the chip could take, which bound binds)."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_bytes = nbytes / p["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
