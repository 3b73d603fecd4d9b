"""Graph and traffic from a configuration, a traffic mix and a seed.

JAX-free: the load generator's process builds the pool with it. The
graph and the vehicles come from the copies in ``ref/synth.py`` (the
program's ``synth`` generator), so no later PR moves them.

A configuration's ``graph`` names the grid; its ``probes`` say how each
vehicle drives and is sampled:

- ``blocks``: [lo, hi] Manhattan distance in blocks between the drive's
  two ends (lo..hi inclusive), so route searches stay local;
- ``sample_period_s``: the sampling periods, one drawn per vehicle;
- ``noise_m``: the GPS noise's standard deviation;
- ``points``: [lo, hi] the trace lengths kept; ``hi`` also bounds the
  decode shapes the warm-up builds.

Every vehicle is drawn from its own ``SeedSequence`` child of
(seed, index), so the pool is the same however many processes build it.
"""
from __future__ import annotations

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from ref import synth  # noqa: E402

#: every drive starts at this time; a /report answer depends on its own
#: trace alone
EPOCH = 1_500_000_000


def build_graph(config: dict, seed: int):
    g = config["graph"]
    return synth.build_grid_city(rows=g["rows"], cols=g["cols"],
                                 spacing_m=g["spacing_m"],
                                 seed=seed % (2 ** 32))


def vehicle(net, config: dict, seed: int, index: int):
    """The ``index``-th vehicle's trace, or None where the drawn drive
    does not qualify (the caller draws the next index)."""
    g, pr = config["graph"], config["probes"]
    rows, cols = g["rows"], g["cols"]
    rng = np.random.default_rng([seed % (2 ** 63), index])
    lo, hi = pr["blocks"]
    r, c = int(rng.integers(0, rows)), int(rng.integers(0, cols))
    d = int(rng.integers(lo, hi + 1))
    dr = int(rng.integers(-d, d + 1))
    dc = (d - abs(dr)) * int(rng.choice((-1, 1)))
    period = float(rng.choice(pr["sample_period_s"]))
    if not (0 <= r + dr < rows and 0 <= c + dc < cols):
        return None
    tr = synth.generate_trace(
        net, f"veh-{seed}-{index}", rng, noise_m=pr["noise_m"],
        sample_period_s=period, start_time=EPOCH,
        min_route_edges=1, max_route_edges=10 ** 6,
        endpoints=(r * cols + c, (r + dr) * cols + c + dc))
    if tr is None:
        return None
    pmin, pmax = pr["points"]
    n = len(tr.points)
    if not pmin <= n <= pmax:
        return None
    return tr
