"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane
is one whose name starts with ``/device:`` (``/device:TPU:0``). On it:

- the ``XLA Ops`` line holds one event per operation that ran; busy time
  is the union of their intervals, so overlapping ops count once;
- the ``XLA Modules`` line holds one event per program run, named
  ``<jit name>(<id>)``; a module's device time is the sum of its events.

Idle gaps are the stretches of the window with no operation on the
device. Each is named by the layer spans (``bench.*``, which ``run.py``
records around the calls into each layer) open on the host at the gap's
midpoint: what the host was doing while the device waited. Host and
device events share the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID = re.compile(r"\(\d+\)$")
#: the prefix of the layer spans run.py records on the host
SPAN_PREFIX = "bench."


def module_name(event_name: str) -> str:
    """``jit_viterbi_assoc_batch(1234)`` -> ``jit_viterbi_assoc_batch``."""
    return _ID.sub("", event_name).strip()


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


class Summary:
    """Device busy time, per-module time and idle gaps of one trace.

    ``window`` is (start_ns, end_ns) on the trace's clock; without it the
    window runs from the first to the last event on any plane.
    """

    def __init__(self, planes, window=None, top: int = 10):
        dev_ops = defaultdict(list)   # device -> [(start, end)]
        modules = defaultdict(float)  # module -> device seconds, summed
        module_calls = defaultdict(int)
        layer = []                    # layer spans: (start, end, name)
        lo, hi = float("inf"), float("-inf")
        for plane in planes:
            is_dev = plane.name.startswith("/device:")
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    lo, hi = min(lo, s), max(hi, e)
                    if is_dev and line.name == OPS_LINE:
                        dev_ops[plane.name].append((s, e))
                    elif is_dev and line.name == MODULES_LINE:
                        m = module_name(ev.name)
                        modules[m] += (e - s) * 1e-9
                        module_calls[m] += 1
                    elif not is_dev and ev.name.startswith(SPAN_PREFIX):
                        layer.append((s, e, ev.name))
        if window is None:
            window = (lo, hi)
        w0, w1 = window
        self.window_s = max(0.0, (w1 - w0) * 1e-9)
        self.devices = sorted(dev_ops)
        busy = []
        gaps = []
        for dev in self.devices:
            spans = [(max(s, w0), min(e, w1)) for s, e in dev_ops[dev]
                     if e > w0 and s < w1]
            merged = _union(spans)
            busy.append(sum(e - s for s, e in merged) * 1e-9)
            edge = w0
            for s, e in merged:
                if s > edge:
                    gaps.append((s - edge, edge, s))
                edge = max(edge, e)
            if w1 > edge:
                gaps.append((w1 - edge, edge, w1))
        #: seconds with an operation running, averaged over the devices
        self.busy_s = sum(busy) / len(busy) if busy else 0.0
        self.modules = dict(modules)
        self.module_calls = dict(module_calls)
        gaps.sort(reverse=True)
        self.gaps = [(self._host_during(layer, (s + e) / 2), (e - s) * 1e-9)
                     for _d, s, e in gaps[:top]]
        self.top = top

    @staticmethod
    def _host_during(spans: list, t: float) -> str:
        active = sorted({n for s, e, n in spans if s <= t < e})
        return "+".join(active) if active else "no layer span"

    @property
    def idle_share(self) -> "float | None":
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of the modules whose name matches ``pattern``
        (a regular expression, searched)."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.modules.items() if rx.search(k))

    def breakdown(self) -> dict:
        ops = sorted(self.modules.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:self.top]],
                "idle_gaps": [[n, s] for n, s in self.gaps]}


def summarise(path: str, window=None, top: int = 10) -> Summary:
    """The :class:`Summary` of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return Summary(ProfileData.from_file(path).planes, window, top)
