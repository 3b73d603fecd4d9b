#!/usr/bin/env python
"""CI perf regression gate: ratios vs the ledger median, never
absolutes.

Fails (exit 1) when a candidate bench run's ``vs_baseline`` ratio —
batched throughput over the SAME box's single-process baseline — falls
more than ``--tolerance`` below the median of comparable ledger
entries, or when a stage's share of wall grows more than
``--share-tolerance`` (absolute) above the ledger median share.
Absolute traces/sec are never compared: bench boxes drift ~2x between
rounds (BENCH_DEV_r06 measured it), and a gate on absolutes would flap
on every box change. This is the "ratio-tolerance mode" ci.yml runs.

Comparable = same ``platform``, a recorded ratio, and (for the share
check) the same ``pipelined`` flag — pipelined stage seconds overlap
the wall, so shares are only meaningful against like-pipelined runs.

Box drift containment: even ratios drift between box draws (the
committed history spans 13.9-27.8 on the same code lineage). An
artifact may therefore carry a same-session ``control`` run — the
prior configuration re-benched on the SAME box. A candidate below the
cross-box floor still passes the ratio check iff the control is ALSO
below the floor (the box provably can't reach the median that day) and
the candidate is within ``--tolerance`` of the control. A healthy box
gets no leniency, and shares/padding/query gates are never relaxed.

Usage:
    # gate a fresh bench artifact (e.g. bench_smoke --out) against the
    # committed ledger
    python tools/perf_gate.py --candidate artifact.json

    # ledger self-consistency: the newest comparable entry gated
    # against the median of the rest (the CI sanity leg)
    python tools/perf_gate.py --self-check

    # change-feed fan-out: zero-silent-loss accounting + fanout floor
    # over a tools/feed_fanout_bench.py artifact (ISSUE 18)
    python tools/perf_gate.py --feed BENCH_FEED_r01.json

    # incremental matcher: per-appended-point decode flatness + zero
    # parity mismatches over a tools/stream_bench.py artifact (ISSUE 19)
    python tools/perf_gate.py --streaming BENCH_STREAM_r01.json

Exit 0 prints the verdict JSON with ``"pass": true``; any regression
prints the offending comparison and exits 1. An empty comparable pool
passes with a note (bootstrap-friendly) unless ``--require-history``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import List, Optional, Tuple

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
from reporter_tpu.obs import ledger as perf_ledger  # noqa: E402

DEFAULT_TOLERANCE = 0.15
DEFAULT_SHARE_TOLERANCE = 0.20


def load_candidate(path: str) -> dict:
    """A candidate entry from either a raw bench.py artifact or an
    already-normalised ledger-entry JSON object."""
    if path == "-":
        d = json.load(sys.stdin)
        source = "stdin"
    else:
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        source = os.path.basename(path)
    if "metric" in d:  # raw bench.py artifact
        return perf_ledger.entry_from_bench(d, source, "candidate",
                                            "bench")
    if "vs_baseline" in d:  # already-normalised ledger entry
        d.setdefault("source", source)
        d.setdefault("stage_shares", None)
        d.setdefault("platform", None)
        d.setdefault("pipelined", None)
        return d
    raise SystemExit(f"candidate {source} is neither a bench artifact "
                     "nor a ledger entry (no vs_baseline)")


def comparable_pool(entries: List[dict], platform: Optional[str],
                    scope: Optional[str] = None) -> List[dict]:
    pool = [e for e in entries
            if e.get("vs_baseline") is not None
            and e.get("kind") in ("bench", "bench_dev")]
    if platform:
        pool = [e for e in pool if e.get("platform") == platform]
    if scope:
        # like-scale only: a 48-trace smoke run's ratio is structurally
        # below a 512-trace run's (amortisation) — never cross-compare
        pool = [e for e in pool if e.get("scope", "full") == scope]
    return pool


def gate(candidate: dict, entries: List[dict], tolerance: float,
         share_tolerance: float, require_history: bool
         ) -> Tuple[bool, dict]:
    """(passed, verdict) — the pure decision, unit-testable."""
    platform = candidate.get("platform")
    scope = candidate.get("scope", "full")
    pool = comparable_pool(entries, platform, scope)
    verdict: dict = {
        "candidate": {"source": candidate.get("source"),
                      "platform": platform, "scope": scope,
                      "vs_baseline": candidate.get("vs_baseline"),
                      "pipelined": candidate.get("pipelined")},
        "pool": len(pool),
        "tolerance": tolerance,
        "share_tolerance": share_tolerance,
        "failures": [],
    }
    if not pool:
        verdict["note"] = ("no comparable ledger entries for platform="
                           f"{platform!r} scope={scope!r}; nothing to "
                           "gate against (append smoke-scope history "
                           "with perf_ledger.py to make this bind)")
        return (not require_history), verdict

    median = statistics.median(e["vs_baseline"] for e in pool)
    floor = median * (1.0 - tolerance)
    verdict["median_vs_baseline"] = round(median, 3)
    verdict["floor"] = round(floor, 3)
    cand_vs = candidate.get("vs_baseline")
    # same-box drift control (BENCH artifacts carry it as a "control"
    # block; ledger entries as control_vs_baseline): the PRIOR
    # configuration re-benched in the same session. When the control
    # itself lands below the cross-box floor the box demonstrably
    # cannot reach the ledger median that day — ratios drift ~2x
    # between box draws just like absolutes (r10 measured it) — so the
    # binding comparison becomes candidate-vs-control on the SAME box.
    # A healthy box (control at/above floor) gets no such leniency.
    ctrl = candidate.get("control_vs_baseline")
    if ctrl is None and isinstance(candidate.get("control"), dict):
        ctrl = candidate["control"].get("vs_baseline")
    if cand_vs is None:
        verdict["failures"].append(
            {"check": "ratio", "reason": "candidate has no vs_baseline "
             "(failed run?)"})
    elif cand_vs < floor:
        if ctrl is not None and ctrl < floor \
                and cand_vs >= ctrl * (1.0 - tolerance):
            verdict["ratio_drift_control"] = {
                "control_vs_baseline": ctrl,
                "control_floor": round(ctrl * (1.0 - tolerance), 3),
                "note": (f"vs_baseline {cand_vs} is below the cross-box "
                         f"floor {floor:.2f}, but the same-box control "
                         f"run only reached {ctrl} — box drift, not a "
                         "code regression; gated against the control "
                         "instead"),
            }
        else:
            verdict["failures"].append(
                {"check": "ratio", "candidate": cand_vs,
                 "median": round(median, 3), "floor": round(floor, 3),
                 "reason": f"vs_baseline {cand_vs} fell more than "
                 f"{tolerance:.0%} below the ledger median {median:.2f}"})

    shares = candidate.get("stage_shares")
    pipelined = candidate.get("pipelined")
    if shares and pipelined is not None:
        like = [e for e in pool
                if e.get("stage_shares")
                and e.get("pipelined") == pipelined]
        share_medians = {}
        for stage in perf_ledger.SHARE_STAGES:
            vals = [e["stage_shares"][stage] for e in like
                    if stage in e["stage_shares"]]
            if vals:
                share_medians[stage] = statistics.median(vals)
        verdict["share_medians"] = {k: round(v, 4)
                                    for k, v in share_medians.items()}
        for stage, cand_share in shares.items():
            med = share_medians.get(stage)
            if med is None:
                continue
            if cand_share > med + share_tolerance:
                verdict["failures"].append(
                    {"check": "share", "stage": stage,
                     "candidate": cand_share, "median": round(med, 4),
                     "reason": f"{stage} share {cand_share} grew more "
                     f"than {share_tolerance} above the ledger median "
                     f"{med:.3f}"})
    return (not verdict["failures"]), verdict


def gate_shares_absolute(candidate: dict, max_shares: dict
                         ) -> Tuple[bool, dict]:
    """Absolute per-stage share ceilings (``--max-share report=0.2``):
    the median gate only catches REGRESSIONS vs history — a ceiling
    pins a stage's share below a hard target (ISSUE 11: the native
    wire writer must hold the serialized ``report`` share at or below
    its acceptance number, not merely match the ledger median)."""
    shares = candidate.get("stage_shares") or {}
    verdict: dict = {"candidate": {"source": candidate.get("source"),
                                   "stage_shares": shares},
                     "max_shares": max_shares, "failures": []}
    for stage, ceil in max_shares.items():
        got = shares.get(stage)
        if got is None:
            verdict["failures"].append(
                {"check": "max_share", "stage": stage,
                 "reason": f"candidate records no {stage!r} share to "
                 "hold under the ceiling"})
        elif got > ceil:
            verdict["failures"].append(
                {"check": "max_share", "stage": stage, "candidate": got,
                 "ceiling": ceil,
                 "reason": f"{stage} share {got} exceeds the hard "
                 f"ceiling {ceil}"})
    return (not verdict["failures"]), verdict


def gate_query_ratio(candidate: dict, floor: float) -> Tuple[bool, dict]:
    """Floor on the serving-tier batched-query speedup
    (``--min-query-ratio 5``): the ISSUE-14 acceptance number — ONE
    ``query_many(256)`` sweep must answer at least ``floor``x faster
    than 256 single queries (bench.py's ``query`` block; answers are
    parity-asserted inside the leg before timing). A candidate without
    the block fails loudly; an explicit ``error`` record fails with the
    recorded reason — a silently missing ratio must never pass a floor
    the caller believes binds."""
    query = candidate.get("query") or {}
    ratio = query.get("batch_ratio")
    verdict: dict = {"candidate": {"source": candidate.get("source"),
                                   "query": query or None},
                     "min_query_ratio": floor, "failures": []}
    if query.get("error"):
        verdict["failures"].append(
            {"check": "query_ratio", "reason": "query leg failed: "
             + str(query["error"])})
    elif ratio is None:
        verdict["failures"].append(
            {"check": "query_ratio", "reason": "candidate records no "
             "query.batch_ratio to hold over the floor"})
    elif ratio < floor:
        verdict["failures"].append(
            {"check": "query_ratio", "candidate": ratio, "floor": floor,
             "reason": f"query_many({query.get('n_segments')}) answered "
             f"only {ratio}x faster than single queries (floor {floor})"})
    return (not verdict["failures"]), verdict


def gate_multichip(path: str, min_ratio: float) -> Tuple[bool, dict]:
    """Gate a tools/multichip_bench.py artifact: every leg ran, ratios
    were measured, and no device count fell below ``min_ratio`` x the
    1-device throughput (on a CPU box the virtual mesh shards compute-
    bound work over the same cores, so the default floor only catches
    a catastrophic sharding regression; raise it on real hardware)."""
    with open(path, encoding="utf-8") as f:
        art = json.load(f)
    ratios = art.get("ratios") or {}
    verdict = {
        "candidate": {"source": os.path.basename(path),
                      "kind": "multichip",
                      "n_devices": art.get("n_devices")},
        "ratios": ratios, "min_ratio": min_ratio, "failures": [],
    }
    if not art.get("ok"):
        verdict["failures"].append(
            {"check": "multichip", "reason": "artifact reports ok=false "
             f"(tail: {art.get('tail', '')[:120]})"})
    if not ratios:
        verdict["failures"].append(
            {"check": "multichip", "reason": "artifact carries no "
             "device-count ratios (legacy liveness-only verdict? "
             "re-run tools/multichip_bench.py)"})
    # the r06 lesson: every leg must have SEEN the device count it
    # claims to measure — an artifact whose legs disagree with their
    # requested counts carries ratios of nothing (the committed r06
    # ratios 0.71-0.89 were exactly this, devices_seen: 1 everywhere)
    for leg in art.get("legs") or []:
        if leg.get("devices_seen") != leg.get("n_devices"):
            verdict["failures"].append(
                {"check": "multichip", "n_devices": leg.get("n_devices"),
                 "devices_seen": leg.get("devices_seen"),
                 "reason": f"leg requested {leg.get('n_devices')} "
                 f"device(s) but saw {leg.get('devices_seen')} — the "
                 "forced host-device count never reached the leg, so "
                 "its throughput ratio is meaningless"})
    for count, ratio in sorted(ratios.items(), key=lambda kv: int(kv[0])):
        if ratio < min_ratio:
            verdict["failures"].append(
                {"check": "multichip", "n_devices": int(count),
                 "candidate": ratio, "floor": min_ratio,
                 "reason": f"{count}-device throughput fell to {ratio}x "
                 f"the 1-device leg (floor {min_ratio})"})
    return (not verdict["failures"]), verdict


def gate_bigreplay(path: str, min_ratio: float) -> Tuple[bool, dict]:
    """Gate a tools/bigreplay.py artifact: the chaos leg's throughput
    over the clean leg's (same process, same box — a true ratio) must
    not fall below ``min_ratio``. This is the "robustness never
    silently costs performance" leg: a fault-path regression (a
    blocking drainer, an over-eager breaker, a spool fsync storm)
    shows up as the chaos leg slowing relative to clean long before it
    shows in clean-path medians."""
    with open(path, encoding="utf-8") as f:
        art = json.load(f)
    if art.get("kind") != "bigreplay":
        raise SystemExit(f"{path} is not a bigreplay artifact")
    ratio = art.get("fault_throughput_ratio")
    verdict = {
        "candidate": {"source": os.path.basename(path),
                      "kind": "bigreplay",
                      "probes": art.get("probes"),
                      "agreement": art.get("agreement")},
        "fault_throughput_ratio": ratio,
        "min_ratio": min_ratio,
        "failures": [],
    }
    if ratio is None:
        verdict["failures"].append(
            {"check": "bigreplay", "reason": "artifact carries no "
             "fault_throughput_ratio (failed run?)"})
    elif ratio < min_ratio:
        verdict["failures"].append(
            {"check": "bigreplay", "candidate": ratio,
             "floor": min_ratio,
             "reason": f"chaos-leg throughput fell to {ratio:.2f}x the "
             f"clean leg (floor {min_ratio}) — the robustness machinery "
             "is taxing the hot path"})
    return (not verdict["failures"]), verdict


def gate_feed(path: str, min_fanout: float) -> Tuple[bool, dict]:
    """Gate a tools/feed_fanout_bench.py artifact: the zero-silent-loss
    contract (ISSUE 18). Every subscriber must be accounted for —
    delivered, shed with the explicit 429 + Retry-After signal, or an
    error — with ``silent_lost == 0`` and ``errors == 0``; the
    accounting must close (delivered + shed + errors + silent_lost ==
    subscribers); and ``fanout_ratio`` must hold ``min_fanout``. A
    missing field fails loudly — an artifact that never counted a
    category must not pass a gate about counting."""
    with open(path, encoding="utf-8") as f:
        art = json.load(f)
    if art.get("kind") != "feed_fanout":
        raise SystemExit(f"{path} is not a feed_fanout artifact")
    verdict = {
        "candidate": {"source": os.path.basename(path),
                      "kind": "feed_fanout",
                      "subscribers": art.get("subscribers"),
                      "procs": art.get("procs"),
                      "delivery_p99_ms": art.get("delivery_p99_ms")},
        "fanout_ratio": art.get("fanout_ratio"),
        "min_fanout_ratio": min_fanout,
        "failures": [],
    }
    fields = ("subscribers", "delivered", "shed", "errors",
              "silent_lost", "fanout_ratio")
    missing = [k for k in fields if art.get(k) is None]
    if missing:
        verdict["failures"].append(
            {"check": "feed", "reason": "artifact is missing "
             f"{missing} — a category that was never counted cannot "
             "pass a loss gate"})
        return False, verdict
    if art["silent_lost"]:
        verdict["failures"].append(
            {"check": "feed", "candidate": art["silent_lost"],
             "floor": 0,
             "reason": f"{art['silent_lost']} subscriber(s) saw "
             "neither the event nor an explicit shed signal — the "
             "zero-silent-loss contract is broken"})
    if art["errors"]:
        verdict["failures"].append(
            {"check": "feed", "candidate": art["errors"], "floor": 0,
             "reason": f"{art['errors']} subscriber(s) errored "
             f"({art.get('error_kinds')})"})
    accounted = art["delivered"] + art["shed"] + art["errors"] \
        + art["silent_lost"]
    if accounted != art["subscribers"]:
        verdict["failures"].append(
            {"check": "feed", "candidate": accounted,
             "floor": art["subscribers"],
             "reason": f"accounting open: delivered+shed+errors+lost "
             f"= {accounted} != {art['subscribers']} subscribers"})
    if art["fanout_ratio"] < min_fanout:
        verdict["failures"].append(
            {"check": "feed", "candidate": art["fanout_ratio"],
             "floor": min_fanout,
             "reason": f"fanout_ratio {art['fanout_ratio']} < floor "
             f"{min_fanout}: the measured commit did not reach enough "
             "of the subscriber fleet"})
    return (not verdict["failures"]), verdict


def gate_streaming(path: str, max_ratio: float) -> Tuple[bool, dict]:
    """Gate a tools/stream_bench.py artifact: the incremental matcher's
    flat-decode contract (ISSUE 19). Per-appended-point decode p99 at
    the longest window over the shortest (``flatness_ratio``) must stay
    within ``max_ratio`` — a carried-state advance whose cost grows
    with the window length is a whole-window re-decode wearing a cache
    — parity mismatches against the batch oracle must be ZERO, and
    every leg must have actually served incrementally (flatness over an
    all-fallback leg is vacuous). A missing field fails loudly."""
    with open(path, encoding="utf-8") as f:
        art = json.load(f)
    if art.get("kind") != "streaming":
        raise SystemExit(f"{path} is not a streaming artifact")
    verdict = {
        "candidate": {"source": os.path.basename(path),
                      "kind": "streaming",
                      "lag": art.get("lag"),
                      "windows": sorted(int(t) for t in
                                        (art.get("legs") or {}))},
        "flatness_ratio": art.get("flatness_ratio"),
        "max_stream_ratio": max_ratio,
        "failures": [],
    }
    legs = art.get("legs") or {}
    missing = [k for k in ("flatness_ratio", "parity_mismatches")
               if art.get(k) is None]
    if not legs or len(legs) < 2:
        missing.append("legs")
    if missing:
        verdict["failures"].append(
            {"check": "streaming", "reason": "artifact is missing "
             f"{missing} — a quantity that was never measured cannot "
             "pass a flatness gate"})
        return False, verdict
    if art["parity_mismatches"]:
        verdict["failures"].append(
            {"check": "streaming", "candidate": art["parity_mismatches"],
             "floor": 0,
             "reason": f"{art['parity_mismatches']} served window(s) "
             "differed from the batch oracle — the byte-parity "
             "contract is broken"})
    for t, leg in sorted(legs.items(), key=lambda kv: int(kv[0])):
        if not leg.get("served"):
            verdict["failures"].append(
                {"check": "streaming", "candidate": 0, "floor": 1,
                 "reason": f"T={t} served no window incrementally — "
                 "its decode timings gate nothing"})
    if art["flatness_ratio"] > max_ratio:
        verdict["failures"].append(
            {"check": "streaming", "candidate": art["flatness_ratio"],
             "ceiling": max_ratio,
             "reason": f"flatness_ratio {art['flatness_ratio']} > "
             f"{max_ratio}: per-appended-point decode cost grows with "
             "the window length"})
    return (not verdict["failures"]), verdict


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="perf_gate",
                                     description=__doc__.splitlines()[0])
    parser.add_argument("--ledger", default=perf_ledger.DEFAULT_LEDGER)
    parser.add_argument("--candidate",
                        help="bench artifact or ledger-entry JSON file "
                        "('-' for stdin)")
    parser.add_argument("--self-check", action="store_true",
                        help="gate the newest comparable ledger entry "
                        "against the median of the rest")
    parser.add_argument("--bigreplay",
                        help="bigreplay artifact: gate the chaos/clean "
                        "throughput ratio against --min-fault-ratio")
    parser.add_argument("--multichip",
                        help="multichip_bench artifact: gate every "
                        "device-count throughput ratio against "
                        "--min-device-ratio")
    parser.add_argument("--feed",
                        help="feed_fanout_bench artifact: gate the "
                        "zero-silent-loss accounting and fanout ratio "
                        "against --min-fanout-ratio")
    parser.add_argument("--streaming",
                        help="stream_bench artifact: gate per-appended-"
                        "point decode flatness against "
                        "--max-stream-ratio and parity mismatches "
                        "against zero")
    parser.add_argument("--max-stream-ratio", type=float, default=1.5,
                        help="ceiling for decode p99 at the longest "
                        "window over the shortest in the --streaming "
                        "gate (default 1.5; parity gates at zero "
                        "regardless)")
    parser.add_argument("--min-fanout-ratio", type=float, default=0.95,
                        help="floor for delivered/subscribers in the "
                        "--feed gate (default 0.95; loss and errors "
                        "gate at zero regardless)")
    parser.add_argument("--min-device-ratio", type=float, default=0.5,
                        help="floor for each N-device over 1-device "
                        "throughput ratio (default 0.5: a CPU box's "
                        "virtual mesh shards the same cores; raise on "
                        "real hardware)")
    parser.add_argument("--max-share", action="append", default=[],
                        metavar="STAGE=CEIL",
                        help="hard absolute ceiling on a candidate "
                        "stage share (repeatable), e.g. report=0.2 — "
                        "checked in addition to the median gate")
    parser.add_argument("--min-query-ratio", type=float, default=None,
                        metavar="FLOOR",
                        help="floor on the candidate's batched-query "
                        "speedup (query.batch_ratio from bench.py's "
                        "query_many-vs-singles pair), e.g. 5")
    parser.add_argument("--min-fault-ratio", type=float, default=0.4,
                        help="floor for the bigreplay chaos-over-clean "
                        "throughput ratio (default 0.4 — small smoke "
                        "runs are noisy; raise it for full-scale runs)")
    parser.add_argument("--tolerance", type=float,
                        default=DEFAULT_TOLERANCE,
                        help="allowed relative vs_baseline drop below "
                        "the ledger median (default 0.15)")
    parser.add_argument("--share-tolerance", type=float,
                        default=DEFAULT_SHARE_TOLERANCE,
                        help="allowed absolute stage-share growth above "
                        "the ledger median (default 0.20)")
    parser.add_argument("--require-history", action="store_true",
                        help="fail instead of passing when no "
                        "comparable entries exist")
    args = parser.parse_args(argv)

    max_shares = {}
    for spec in args.max_share:
        try:
            stage, ceil = spec.split("=", 1)
            max_shares[stage.strip()] = float(ceil)
        except ValueError:
            parser.error(f"--max-share wants STAGE=CEIL, got {spec!r}")
    if (max_shares or args.min_query_ratio is not None) \
            and (args.bigreplay or args.multichip or args.feed):
        # those artifacts carry no stage shares / query block — refuse
        # loudly rather than silently ignoring a ceiling the caller
        # believes binds
        parser.error("--max-share/--min-query-ratio apply to "
                     "--candidate/--self-check runs only")

    if args.bigreplay:
        passed, verdict = gate_bigreplay(args.bigreplay,
                                         args.min_fault_ratio)
        verdict["pass"] = passed
        print(json.dumps(verdict, separators=(",", ":")))
        if not passed:
            for f in verdict["failures"]:
                sys.stderr.write(f"perf_gate: FAIL: {f['reason']}\n")
        return 0 if passed else 1

    if args.feed:
        passed, verdict = gate_feed(args.feed, args.min_fanout_ratio)
        verdict["pass"] = passed
        print(json.dumps(verdict, separators=(",", ":")))
        if not passed:
            for f in verdict["failures"]:
                sys.stderr.write(f"perf_gate: FAIL: {f['reason']}\n")
        return 0 if passed else 1

    if args.streaming:
        passed, verdict = gate_streaming(args.streaming,
                                         args.max_stream_ratio)
        verdict["pass"] = passed
        print(json.dumps(verdict, separators=(",", ":")))
        if not passed:
            for f in verdict["failures"]:
                sys.stderr.write(f"perf_gate: FAIL: {f['reason']}\n")
        return 0 if passed else 1

    if args.multichip:
        passed, verdict = gate_multichip(args.multichip,
                                         args.min_device_ratio)
        verdict["pass"] = passed
        print(json.dumps(verdict, separators=(",", ":")))
        if not passed:
            for f in verdict["failures"]:
                sys.stderr.write(f"perf_gate: FAIL: {f['reason']}\n")
        return 0 if passed else 1

    entries = perf_ledger.load_ledger(args.ledger)
    if args.self_check:
        # the BINDING leg: gate the newest full-scope entry (the
        # committed-artifact lineage) against the median of the rest,
        # with an empty pool counting as failure — appended smoke-scope
        # history must neither become the candidate (its first entry
        # would have no pool and pass vacuously) nor break this leg
        pool = comparable_pool(entries, None, "full") \
            or comparable_pool(entries, None)
        if not pool:
            print(json.dumps({"pass": False,
                              "note": "self-check: empty ledger"}))
            return 1
        candidate = pool[-1]  # newest (ledger is append-only)
        rest = [e for e in entries if e is not candidate]
        passed, verdict = gate(candidate, rest, args.tolerance,
                               args.share_tolerance,
                               require_history=True)
    elif args.candidate:
        candidate = load_candidate(args.candidate)
        passed, verdict = gate(candidate, entries, args.tolerance,
                               args.share_tolerance,
                               args.require_history)
    else:
        parser.error("need --candidate FILE, --self-check, "
                     "--bigreplay FILE, --multichip FILE, "
                     "--feed FILE or --streaming FILE")
        return 2  # unreachable; parser.error exits

    if max_shares:  # absolute ceilings, on top of the median gate
        abs_ok, abs_verdict = gate_shares_absolute(candidate, max_shares)
        verdict["max_shares"] = abs_verdict["max_shares"]
        verdict["failures"].extend(abs_verdict["failures"])
        passed = passed and abs_ok

    if args.min_query_ratio is not None:
        qr_ok, qr_verdict = gate_query_ratio(candidate,
                                             args.min_query_ratio)
        verdict["min_query_ratio"] = args.min_query_ratio
        verdict["failures"].extend(qr_verdict["failures"])
        passed = passed and qr_ok

    verdict["pass"] = passed
    print(json.dumps(verdict, separators=(",", ":")))
    if not passed:
        for fail in verdict["failures"]:
            sys.stderr.write(f"perf_gate: FAIL: {fail['reason']}\n")
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
