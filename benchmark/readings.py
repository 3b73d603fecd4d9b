"""What a per-layer metric's reader is given: the program's counters and
timers over the window, the kernel calls the window made (by kind and
shape, ``harness.CallSpy``) and the reduced profiler trace of the traced
window (None with ``--trace 0``).

A reader returns a number, or None where it finds nothing to read; the
harness then leaves its metric out of the result line. A share of a
roofline is never returned as 0 for want of a reading.
"""
from __future__ import annotations

from roofline import kernels, min_seconds


class Readings:
    def __init__(self, counters: dict, timers: dict, calls: dict,
                 trace, device_kind: str):
        self.counters = counters    # name -> delta over the window
        self.timers = timers        # name -> (count, total seconds)
        self.calls = calls          # (kind, shape...) -> calls
        self.trace = trace          # devtrace.Summary or None
        self.device_kind = device_kind
        self.kernels = kernels()

    def counter(self, name: str) -> int:
        return self.counters.get(name, 0)

    def timer_total(self, name: str) -> "float | None":
        count, total = self.timers.get(name, (0, 0.0))
        return total if count else None

    def ratio(self, num: "float | None", den: float,
              scale: float = 1.0) -> "float | None":
        if num is None or not den:
            return None
        return num / den * scale

    def idle_share(self) -> "float | None":
        if self.trace is None or self.trace.idle_share is None:
            return None
        return 100.0 * self.trace.idle_share

    def kernel_seconds(self, kind: str) -> "float | None":
        """Device seconds of the kernel's modules in the traced window."""
        if self.trace is None:
            return None
        s = self.trace.module_seconds(self.kernels[kind].MODULES)
        return s if s > 0 else None

    def roofline(self, kind: str) -> "float | None":
        """Least time the window's calls of ``kind`` need at the chip's
        peaks (their ``work``), over their device time, in percent."""
        dev = self.kernel_seconds(kind)
        if dev is None:
            return None
        work = self.kernels[kind].work
        need = sum(n * min_seconds(*work(*key[1:]), self.device_kind)[0]
                   for key, n in self.calls.items() if key[0] == kind)
        return 100.0 * need / dev if need > 0 else None
