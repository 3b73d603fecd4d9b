"""The plain reference of one ``/report`` answer, end to end.

Candidate search (``spatial``), kept points and route costs (``prep``,
``route``), the Viterbi decode (``viterbi``), segment assembly
(``assemble``) and the report writer (``report``): copies of the
program's numpy path, importing nothing of ``reporter_tpu``.

The precision is the configuration's ``precision``: distances reach the
decode as float16 (the program's default wire, ``REPORTER_TPU_WIRE``;
a trace with a finite distance above ``WIRE_MAX_M`` ships float32) and
are scored in float32. The control of the correctness check
(``ref.control``) runs the same pipeline one step down in both: an
8-bit float wire and bfloat16 scores.
"""
from __future__ import annotations

import json
from types import SimpleNamespace

import ml_dtypes
import numpy as np

from .assemble import assemble_segments
from .report import report
from .prep import _prepare_from_candidates
from .route import RouteCache
from .spatial import PAD_EDGE, SpatialGrid
from .viterbi import viterbi_decode_numpy

#: MatchParams defaults (reporter_tpu/matcher/params.py), the reference
#: deployment's Meili settings (Dockerfile:14-17)
PARAM_DEFAULTS = {
    "mode": "auto", "sigma_z": 4.07, "beta": 3.0,
    "max_route_distance_factor": 5.0, "max_route_time_factor": 2.0,
    "min_time_bound_s": 15.0, "breakage_distance": 2000.0,
    "search_radius": 50.0, "turn_penalty_factor": 0.0, "gps_accuracy": 0.0,
    "max_candidates": 8, "interpolation_distance": 10.0,
    "backward_tolerance_m": 25.0, "queue_speed_threshold_kph": 10.0,
}


def match_params(overrides: dict) -> SimpleNamespace:
    p = dict(PARAM_DEFAULTS)
    p.update(overrides)
    sigma = p["sigma_z"]
    if p["gps_accuracy"] and p["gps_accuracy"] > 0:
        sigma = max(sigma, p["gps_accuracy"] / 1.96)
    return SimpleNamespace(effective_sigma=sigma, **p)


#: largest finite distance the float16 wire ships (matcher/hmm.py)
WIRE_MAX_M = 4.096e3
UNREACHABLE_THRESHOLD = 0.5e9


def _wire_f16(prep) -> bool:
    """Whether the trace's finite distances all fit the float16 wire."""
    for arr in (prep.gc_m, prep.route_m, prep.dist_m):
        if arr.size and float(np.amax(
                arr, initial=0.0,
                where=arr < UNREACHABLE_THRESHOLD)) > WIRE_MAX_M:
            return False
    return True


#: the wire dtypes: the program's float16, and the control's 8-bit float
#: (e5m2, the 8-bit float whose range holds the 4 km the wire ships)
WIRE_DTYPES = {"f16": np.float16, "fp8": ml_dtypes.float8_e5m2}


def _on_wire(x, wire: str):
    with np.errstate(over="ignore"):
        return np.asarray(x, np.float32).astype(WIRE_DTYPES[wire]).astype(
            np.float32)


class Reference:
    """Answers ``/report`` requests on one graph, one request at a time."""

    def __init__(self, net, matcher: dict, threshold_sec: float,
                 decode=viterbi_decode_numpy, wire: str = "f16"):
        self.net = net
        self.params = match_params(matcher)
        self.threshold_sec = threshold_sec
        self.grid = SpatialGrid(net, cell_m=75.0)
        self.cache = RouteCache(net)
        self.decode = decode
        self.wire = wire

    def match(self, req: dict) -> dict:
        p = self.params
        pts = req["trace"]
        lat = np.array([q["lat"] for q in pts], dtype=np.float64)
        lon = np.array([q["lon"] for q in pts], dtype=np.float64)
        times = np.array([q["time"] for q in pts], dtype=np.float64)
        cands = self.grid.candidates(lat, lon, p.max_candidates,
                                     p.search_radius)
        has = (cands.edge_ids != PAD_EDGE).any(axis=1)
        prep = _prepare_from_candidates(self.net, lat, lon, times, cands,
                                        has, p, self.cache, None)
        if prep.num_kept:
            dist, route, gc = prep.dist_m, prep.route_m, prep.gc_m
            if self.wire in WIRE_DTYPES and _wire_f16(prep):
                dist, route, gc = (_on_wire(a, self.wire)
                                   for a in (dist, route, gc))
            path, _score = self.decode(
                dist, prep.edge_ids != PAD_EDGE, route, gc, prep.case,
                p.effective_sigma, p.beta)
        else:
            path = np.zeros(prep.T, dtype=np.int32)
        return assemble_segments(
            self.net, prep, path, mode=p.mode,
            queue_threshold_kph=p.queue_speed_threshold_kph,
            interpolation_distance_m=p.interpolation_distance,
            backward_tolerance_m=p.backward_tolerance_m,
            turn_penalty_factor=p.turn_penalty_factor)

    def report(self, req: dict) -> dict:
        opts = req["match_options"]
        body = report(self.match(req), req, self.threshold_sec,
                      set(opts["report_levels"]),
                      set(opts["transition_levels"]))
        # the served body is JSON: compare like with like
        return json.loads(json.dumps(body, separators=(",", ":")))
