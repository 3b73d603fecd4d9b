"""A fleet's probe backlog as one time-ordered stream, from a
configuration, a traffic mix and a seed.

JAX-free, and built on the copies in ``ref/synth.py``: the graph is
``gen.build_graph``'s. Each vehicle comes from its own
``SeedSequence(seed, index)``:

- it starts at a node drawn uniformly from its own ``1 / vehicles``
  share of the grid's nodes in row-major order (vehicle ``i`` from the
  ``i``-th share), at a whole second drawn uniformly from the first
  ``start_s`` seconds after ``gen.EPOCH``. The stratified start is a
  variance reduction of the benchmark, not a picture of a partition,
  whose uuid-hashed vehicles start independently: each start is still
  uniform over the city, so the mean is unchanged, but a seed's fleet
  cannot crowd onto or off the arterials. How many windows a vehicle
  forms depends on its streets (3 to 46 in a 96-vehicle sample); with
  independent starts the fleet's window count spread 4.5% over seeds,
  with these 2.6% (eight seeds each);
- it drives one trip of ``trip_s`` [lo, hi] probes, one every
  ``sample_period_s``: chained shortest-path legs driven and sampled as
  the configuration's ``probes`` group says (``blocks`` [lo, hi] between
  a leg's ends, ``noise_m``).

The stream holds every vehicle's probes merged by probe time, ties by
uuid, as the messages the worker's ``sv`` formatter
``,sv,\\|,0,1,2,3,4`` reads: ``uuid|lat|lon|epoch seconds|accuracy``.
"""
from __future__ import annotations

import numpy as np

import gen
from ref import synth

#: the formatter configuration of the stream's messages
FORMATTER = ",sv,\\|,0,1,2,3,4"
#: the warm-up's vehicles are drawn from indices of their own
WARM_FIRST = 1 << 40


def uuid_of(seed: int, index: int) -> str:
    return f"veh-{seed}-{index}"


def _leg_end(rng, node: int, rows: int, cols: int, blocks) -> "int | None":
    r, c = divmod(node, cols)
    d = int(rng.integers(blocks[0], blocks[1] + 1))
    dr = int(rng.integers(-d, d + 1))
    dc = (d - abs(dr)) * int(rng.choice((-1, 1)))
    if not (0 <= r + dr < rows and 0 <= c + dc < cols):
        return None
    return (r + dr) * cols + c + dc


def trip(net, config: dict, traffic: dict, seed: int, index: int,
         points: "int | None" = None) -> list:
    """The ``index``-th vehicle's trip: probe dicts (lat, lon, time,
    accuracy) in time order; ``points`` of them where given, else a
    number drawn from ``trip_s``."""
    g, pr = config["graph"], config["probes"]
    rows, cols = g["rows"], g["cols"]
    period = traffic["sample_period_s"]
    rng = np.random.default_rng([seed % (2 ** 63), index])
    fleet = traffic["vehicles"]
    node = int((index % fleet + rng.random()) / fleet * rows * cols)
    t = gen.EPOCH + int(rng.integers(0, traffic["start_s"]))
    lo, hi = traffic["trip_s"]
    want = points or int(rng.integers(lo, hi + 1)) // period
    uuid = uuid_of(seed, index)
    out = []
    while len(out) < want:
        dest = _leg_end(rng, node, rows, cols, pr["blocks"])
        if dest is None or dest == node:
            continue
        tr = synth.generate_trace(
            net, uuid, rng, noise_m=pr["noise_m"], sample_period_s=period,
            start_time=t, min_route_edges=1, max_route_edges=10 ** 6,
            endpoints=(node, dest))
        if tr is None:
            continue
        out += tr.points
        node = dest
        t = out[-1]["time"] + period
    return out[:want]


def backlog(net, config: dict, traffic: dict, seed: int) -> tuple:
    """(the stream's messages in order, {vehicles, probes})."""
    probes = []
    for i in range(traffic["vehicles"]):
        uuid = uuid_of(seed, i)
        probes += [(p["time"], uuid, p)
                   for p in trip(net, config, traffic, seed, i)]
    probes.sort(key=lambda x: (x[0], x[1]))
    lines = [f"{uuid}|{p['lat']!r}|{p['lon']!r}|{t}|{p['accuracy']}"
             for t, uuid, p in probes]
    return lines, {"vehicles": traffic["vehicles"], "probes": len(lines)}


def warm_trips(net, config: dict, traffic: dict, seed: int, n: int,
               points: int) -> list:
    """``n`` trips of ``points`` probes, from the warm-up's own vehicle
    indices."""
    return [trip(net, config, traffic, seed, WARM_FIRST + i, points)
            for i in range(n)]
