"""The perf ledger: normalised bench history as one entry schema.

The repo accumulated perf history as loose artifacts — ``BENCH_r0*``
(driver rounds), ``BENCH_DEV_r0*`` (developer-recorded runs, sometimes
several legs per file), ``MULTICHIP_r0*`` (mesh harness verdicts) — in
four different JSON shapes that no CI stage read. This module is the
library half of the fix (``tools/perf_ledger.py`` is the CLI,
``tools/perf_gate.py`` the CI regression gate): every artifact
flattens to one line of ``LEDGER.jsonl``:

    {"source": file, "label": round, "kind": bench|bench_dev|multichip,
     "scope": full|smoke, "platform": cpu|tpu|None,
     "decode": scan|assoc|None, "pipelined": bool|None,
     "vs_baseline": ratio|None, "traces_per_sec": N|None,
     "baseline_tps": N|None, "stage_shares": {stage: s/total}|None,
     "n_devices": N|None, "ok": bool|None, "context": note|None}

Three rules the gate depends on:

- **Ratios, never absolutes.** Bench boxes drift ~2x between rounds
  (BENCH_DEV_r06's context block measured it), so entries carry
  ``vs_baseline`` (batched/baseline on the SAME box) and per-stage
  *shares* of wall — the numbers that survive a box change.
- **Like scope only.** A bench_smoke-sized run (tiny batch, one
  repeat) has a structurally lower ratio than a 512-trace run —
  batching amortisation hasn't kicked in (measured: 0.57 at 48 traces
  on a 2-core CI box vs 18+ at 512 on dev boxes) — so entries carry a
  ``scope`` and the gate never cross-compares. Likewise a stage whose
  *measurement scope* changed (PR 4 folded response serialisation
  into ``report``) drops its legacy share rather than comparing two
  different quantities.
- **Context rides along.** Each artifact's box-drift note is carried
  into the entry verbatim, so a future reader of a surprising ratio
  sees the caveat next to the number.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import List, Optional

DEFAULT_LEDGER = "LEDGER.jsonl"

#: stages whose share of total wall the gate compares (the bench
#: breakdown's stable subset; prep phase sub-splits are diagnostic)
SHARE_STAGES = ("prep", "decode_dispatch", "decode_wait", "assemble",
                "report")

_METRIC_RE = re.compile(r"platform=(\w+), decode=(\w+)")


def stage_shares(stages: Optional[dict]) -> Optional[dict]:
    """Per-stage share of total wall from a bench ``stages`` block;
    None when the block is missing or carries no total. Shares on a
    pipelined run can sum past 1.0 (stages overlap) — the gate only
    compares like-pipelined entries."""
    if not stages:
        return None
    total = stages.get("total")
    if not total:
        return None
    out = {}
    for name in SHARE_STAGES:
        val = stages.get(name)
        if isinstance(val, (int, float)):
            out[name] = round(val / total, 4)
    return out or None


def entry_from_bench(parsed: dict, source: str, label: str, kind: str,
                     context: Optional[str] = None) -> dict:
    """One ledger entry from a bench.py output object."""
    metric = parsed.get("metric") or ""
    m = _METRIC_RE.search(metric)
    stages = parsed.get("stages") or {}
    baseline = parsed.get("baseline") or {}
    pipelined = stages.get("pipelined")
    shares = stage_shares(stages)
    # PR 4 widened the bench's ``report`` stage to include full
    # response serialisation (the metric string says
    # "report-serialise" since). A legacy entry's report share is a
    # DIFFERENT measurement — gating the new scope against it reads as
    # a 4x regression that never happened — so it is dropped, not
    # compared. Every other stage kept its scope.
    if shares and "report-serialise" not in metric:
        shares.pop("report", None)
    # run scale: tiny runs gate only against tiny-run history (see
    # module doc)
    base_n = (baseline.get("n_traces")
              if isinstance(baseline.get("n_traces"), int) else None)
    scope = "smoke" if base_n is not None and base_n < 64 else "full"
    entry = {
        "source": source,
        "label": label,
        "kind": kind,
        "scope": scope,
        "platform": m.group(1) if m else None,
        "decode": m.group(2) if m else None,
        "pipelined": pipelined if isinstance(pipelined, bool) else None,
        "vs_baseline": parsed.get("vs_baseline"),
        "traces_per_sec": parsed.get("value"),
        "baseline_tps": baseline.get("traces_per_sec"),
        "stage_shares": shares,
        "n_devices": None,
        "ok": parsed.get("vs_baseline") is not None,
        "context": context,
    }
    # the serving-tier batched-query pair (ISSUE 14): query_many(256)
    # vs 256 single queries over the same store — another same-box
    # ratio pair, gated by perf_gate --min-query-ratio
    if isinstance(parsed.get("query"), dict):
        entry["query"] = parsed["query"]
    # the route-kernel triple (ISSUE 16): device relax vs host Dijkstra
    # vs native memo on identical pairs (parity asserted before timing)
    # — the device/host ratio is the prep_routes speedup the pipelined
    # shares should reflect
    if isinstance(parsed.get("routes"), dict):
        entry["routes"] = parsed["routes"]
    return entry


def _failed_entry(source: str, label: str, kind: str, tail: str) -> dict:
    return {"source": source, "label": label, "kind": kind,
            "scope": "full", "platform": None, "decode": None,
            "pipelined": None, "vs_baseline": None,
            "traces_per_sec": None, "baseline_tps": None,
            "stage_shares": None, "n_devices": None, "ok": False,
            "context": ("run failed: "
                        + (tail.strip().splitlines() or ["?"])[-1][:200])}


def _multichip_entry(source: str, d: dict) -> dict:
    """One ledger entry from a MULTICHIP artifact. r01-r05 carry only
    ``ok: true`` — a liveness verdict with no measurement — and are
    tagged ``scope: legacy`` so no gate median ever pools them with
    measured runs (the like-for-like pool starts at the first artifact
    whose legs assert ``devices_seen``); tools/multichip_bench.py
    artifacts add per-device-count legs and throughput ratios —
    ``vs_baseline`` then holds the max-device-count ratio over the
    1-device leg (a true same-box ratio, like every other entry) and
    ``traces_per_sec`` the max-device leg's absolute, with the full
    ratio curve in context. A measured artifact whose legs never saw
    their requested device count (the r06 failure mode) is also tagged
    legacy: its ratios compare nothing. Gate with ``tools/perf_gate.py
    --multichip`` (the kind is excluded from the bench comparable pool,
    so these ratios never bleed into the vs_baseline medians)."""
    ratios = d.get("ratios") or {}
    legs = d.get("legs") or []
    top = max((leg for leg in legs
               if leg.get("traces_per_sec")),
              key=lambda leg: leg["n_devices"], default=None)
    vs = ratios.get(str(d.get("n_devices"))) if ratios else None
    measured = bool(ratios) and all(
        leg.get("devices_seen") == leg.get("n_devices") for leg in legs)
    context = None
    if ratios:
        context = "device ratios vs 1: " + ",".join(
            f"{k}x={v}" for k, v in sorted(ratios.items(),
                                           key=lambda kv: int(kv[0])))
        if not measured:
            context += ("; LEGACY: legs disagree with their requested "
                        "device counts (devices_seen) — ratios compare "
                        "nothing")
    elif not d.get("ok"):
        context = f"rc={d.get('rc')}; harness leg failed or timed out"
    return {"source": source,
            "label": source.replace("MULTICHIP_", "").replace(".json",
                                                              ""),
            "kind": "multichip",
            "scope": "full" if measured else "legacy",
            "platform": None, "decode": None, "pipelined": None,
            "vs_baseline": vs if measured else None,
            "traces_per_sec": top["traces_per_sec"] if top else None,
            "baseline_tps": None, "stage_shares": None,
            "n_devices": d.get("n_devices"), "ok": bool(d.get("ok")),
            "context": context}


def _bigreplay_entry(source: str, d: dict) -> dict:
    """One ledger entry from a tools/bigreplay.py artifact (the ISSUE
    15 scaled-probe legs). ``vs_baseline`` holds the chaos-over-clean
    throughput ratio — a true same-process, same-box ratio like every
    other entry — and the context carries the oracle agreement and
    probe scale. Kind ``bigreplay`` is excluded from the bench
    comparable pool (tools/perf_gate.py ``comparable_pool``), so these
    ratios never bleed into vs_baseline medians; gate them with
    ``perf_gate --bigreplay --min-fault-ratio`` instead. Scope follows
    the probe count: the 100k+ local leg is ``full``, CI-scale runs
    are ``smoke`` (never cross-compared, same rule as bench)."""
    probes = d.get("probes") or 0
    ratio = d.get("fault_throughput_ratio")
    clean = d.get("clean") or {}
    return {"source": source,
            "label": source.replace("BIGREPLAY_", "")
            .replace(".json", ""),
            "kind": "bigreplay",
            "scope": "full" if probes >= 100_000 else "smoke",
            "platform": "cpu", "decode": None, "pipelined": None,
            "vs_baseline": ratio,
            "traces_per_sec": clean.get("probes_per_s"),
            "baseline_tps": None, "stage_shares": None,
            "n_devices": None, "ok": bool(ratio),
            "context": f"probes={probes} agreement={d.get('agreement')}"
                       f" writers={d.get('writers')}"}


def _feed_entry(source: str, d: dict) -> dict:
    """One ledger entry from a tools/feed_fanout_bench.py artifact
    (the ISSUE 18 freshness-tier fan-out leg). ``vs_baseline`` holds
    the fanout ratio — delivered subscribers over subscribers, 1.0
    when every long-poll received the measured commit — and the
    context carries delivery p99 and the shed/loss accounting. Kind
    ``feed_fanout`` is excluded from the bench comparable pool
    (tools/perf_gate.py ``comparable_pool``); gate with ``perf_gate
    --feed`` instead. Scope follows subscriber count: the >= 1000
    acceptance leg is ``full``, CI-scale runs are ``smoke``."""
    subs = d.get("subscribers") or 0
    return {"source": source,
            "label": source.replace("BENCH_", "").replace(".json", ""),
            "kind": "feed_fanout",
            "scope": "full" if subs >= 1000 else "smoke",
            "platform": "cpu", "decode": None, "pipelined": None,
            "vs_baseline": d.get("fanout_ratio"),
            "traces_per_sec": None,
            "baseline_tps": None, "stage_shares": None,
            "n_devices": None,
            "ok": d.get("silent_lost") == 0 and not d.get("errors"),
            "context": f"subscribers={subs} procs={d.get('procs')}"
                       f" delivered={d.get('delivered')}"
                       f" shed={d.get('shed')}"
                       f" shed_events={d.get('shed_events')}"
                       f" errors={d.get('errors')}"
                       f" silent_lost={d.get('silent_lost')}"
                       f" p99_ms={d.get('delivery_p99_ms')}"}


def _stream_entry(source: str, d: dict) -> dict:
    """One ledger entry from a tools/stream_bench.py artifact (the
    ISSUE 19 incremental-matcher leg). ``vs_baseline`` holds the
    flatness ratio — per-appended-point decode p99 at the longest
    window over the shortest, <= 1.5 meaning the carried-state cost is
    flat in T while the context's ``growth`` shows the whole-window
    path scaling with it — and ``ok`` pins the zero-parity-mismatch
    contract. Kind ``streaming`` is excluded from the bench comparable
    pool (tools/perf_gate.py ``comparable_pool``); gate with
    ``perf_gate --streaming`` instead. Scope follows the longest
    window: the T=256 acceptance leg is ``full``, shorter smoke runs
    are ``smoke``."""
    legs = d.get("legs") or {}
    t_max = max((int(t) for t in legs), default=0)
    big = legs.get(str(t_max), {})
    return {"source": source,
            "label": source.replace("BENCH_", "").replace(".json", ""),
            "kind": "streaming",
            "scope": "full" if t_max >= 256 else "smoke",
            "platform": "cpu", "decode": "incremental",
            "pipelined": None,
            "vs_baseline": d.get("flatness_ratio"),
            "traces_per_sec": None,
            "baseline_tps": None, "stage_shares": None,
            "n_devices": None,
            "ok": d.get("parity_mismatches") == 0,
            "context": f"windows={sorted(int(t) for t in legs)}"
                       f" lag={d.get('lag')}"
                       f" dec_p99_ms={big.get('dec_p99_ms')}"
                       f" match_p99_ms={big.get('inc_p99_ms')}"
                       f" growth={d.get('batch_growth')}"
                       f" speedup_p50={d.get('speedup_p50_at_256')}"
                       f" mismatches={d.get('parity_mismatches')}"}


def seed_entries(repo: str) -> List[dict]:
    """Normalise every checked-in perf artifact into ledger entries."""
    entries: List[dict] = []

    # driver rounds: {"n", "cmd", "rc", "tail", "parsed"}
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        name = os.path.basename(path)
        label = name.replace("BENCH_", "").replace(".json", "")
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        parsed = d.get("parsed")
        if parsed:
            entries.append(entry_from_bench(parsed, name, label, "bench"))
        else:
            entries.append(_failed_entry(name, label, "bench",
                                         d.get("tail", "")))

    # r06 onward share one shape: {"parsed": <bench artifact>,
    # "serialized_breakdown": {"value", "stages"}, "context": {"box"}}
    # — two entries per file: the pipelined headline and the
    # serialized stage breakdown (whose ratio shares the parsed leg's
    # baseline run — same box, so it is the r05-comparable number)
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BENCH_DEV_r*.json"))):
        name = os.path.basename(path)
        label_n = name.replace("BENCH_DEV_", "").replace(".json", "")
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        box_note = (d.get("context") or {}).get("box")
        # same-session drift control (r10+): the prior configuration
        # re-benched on the same box; perf_gate uses it to tell box
        # drift from a code regression when the ratio lands below the
        # cross-box floor
        ctrl_vs = (d.get("control") or {}).get("vs_baseline") \
            if isinstance(d.get("control"), dict) else None
        if d.get("parsed"):
            e = entry_from_bench(
                d["parsed"], name, f"dev_{label_n}", "bench_dev",
                context=box_note)
            if ctrl_vs is not None:
                e["control_vs_baseline"] = ctrl_vs
            entries.append(e)
        ser = d.get("serialized_breakdown") or {}
        parsed = d.get("parsed") or {}
        base = (parsed.get("baseline") or {}).get("traces_per_sec")
        if ser.get("value") and base:
            shares = stage_shares(ser.get("stages"))
            if shares and "report-serialise" not in \
                    (parsed.get("metric") or ""):
                shares.pop("report", None)  # pre-PR-4 report scope
            # a handful of checked-in artifacts at seed time, not a
            # serving path
            se = {  # lint: ignore[HP002]
                "source": name,
                "label": f"dev_{label_n}_serialized",
                "kind": "bench_dev",
                "scope": "full",
                "platform": "cpu", "decode": "scan",
                "pipelined": False,
                "vs_baseline": round(ser["value"] / base, 2),
                "traces_per_sec": ser["value"],
                "baseline_tps": base,
                "stage_shares": shares,
                "n_devices": None, "ok": True,
                "context": box_note,
            }
            if ctrl_vs is not None:
                se["control_vs_baseline"] = ctrl_vs
            entries.append(se)

    # multichip harness verdicts: {"n_devices", "rc", "ok", ...}
    for path in sorted(glob.glob(os.path.join(repo,
                                              "MULTICHIP_r*.json"))):
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        entries.append(_multichip_entry(os.path.basename(path), d))

    # bigreplay scaled-probe verdicts (ISSUE 15): the chaos/clean
    # throughput ratio + agreement at production-fidelity scale
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BIGREPLAY_r*.json"))):
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        entries.append(_bigreplay_entry(os.path.basename(path), d))

    # change-feed fan-out verdicts (ISSUE 18): subscriber delivery
    # accounting + latency through the pre-fork fleet
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BENCH_FEED_r*.json"))):
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        entries.append(_feed_entry(os.path.basename(path), d))

    # incremental-matcher streaming verdicts (ISSUE 19): per-appended-
    # point decode flatness + parity against the whole-window oracle
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BENCH_STREAM_r*.json"))):
        with open(path, encoding="utf-8") as f:
            d = json.load(f)
        entries.append(_stream_entry(os.path.basename(path), d))
    return entries


def load_ledger(path: str) -> List[dict]:
    entries = []
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i}: not JSON: {e}") from None
    return entries


def write_ledger(path: str, entries: List[dict]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        for e in entries:
            f.write(json.dumps(e, separators=(",", ":")) + "\n")
