"""reporter-lint suite tests: every pass fires on its known-bad fixture,
stays silent on the matching known-good one, the ABI cross-check catches
an injected mismatch against the LIVE pair, and a repo-wide run is clean
against the committed baseline (no new findings, no stale entries).
"""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
sys.path.insert(0, REPO)

from reporter_tpu import analysis                      # noqa: E402
from reporter_tpu.analysis import (abi, durability, fallback,  # noqa: E402
                                   fault_coverage, hotpath, jit_hygiene,
                                   lockgraph, locks, placement, registry,
                                   registry_drift, tensorcontract)
from reporter_tpu.analysis.core import SourceFile, parse_suppressions  # noqa: E402

LIVE_CPP = os.path.join(REPO, abi.DEFAULT_CPP)
LIVE_PY = os.path.join(REPO, abi.DEFAULT_PY)


def _fixture(name: str, relpath: str) -> SourceFile:
    """Load a fixture under a fake repo-relative path so the passes'
    module-scope filters apply."""
    sf = SourceFile.load(os.path.join(FIXTURES, name), REPO)
    sf.relpath = relpath
    return sf


def _run_pass(pass_mod, name: str, relpath: str):
    sf = _fixture(name, relpath)
    findings = analysis.filter_suppressed(pass_mod.run([sf], REPO), [sf])
    return sf, findings


def _expected_lines(sf: SourceFile, rule: str):
    """Lines whose trailing comment names the rule (fixture convention:
    ``# HP001: why`` / ``# JH001 (x2): why``)."""
    out = {}
    for i, line in enumerate(sf.text.splitlines(), start=1):
        m = re.search(rf"#\s*{rule}(?:\s*\(x(\d+)\))?:", line)
        if m:
            out[i] = int(m.group(1) or 1)
    return out


def _assert_matches_annotations(sf, findings, rules):
    got = {}
    for f in findings:
        got.setdefault(f.rule, {}).setdefault(f.line, 0)
        got[f.rule][f.line] += 1
    for rule in rules:
        assert got.get(rule, {}) == _expected_lines(sf, rule), \
            f"{rule} findings diverge from fixture annotations"


# ---- hot-path purity -------------------------------------------------------

def test_hotpath_fires_on_bad_fixture():
    sf, findings = _run_pass(hotpath, "hotpath_bad.py",
                             "reporter_tpu/matcher/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("HP001", "HP002", "HP003"))


def test_hotpath_silent_on_good_fixture():
    _, findings = _run_pass(hotpath, "hotpath_good.py",
                            "reporter_tpu/matcher/fixture_good.py")
    assert findings == []


def test_hotpath_scope_is_declared_module_set():
    # the same bad code OUTSIDE the declared hot-path set is not flagged
    _, findings = _run_pass(hotpath, "hotpath_bad.py",
                            "reporter_tpu/tools/fixture_bad.py")
    assert findings == []


# ---- jit hygiene -----------------------------------------------------------

def test_jit_fires_on_bad_fixture():
    sf, findings = _run_pass(jit_hygiene, "jit_bad.py",
                             "reporter_tpu/ops/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("JH001", "JH002", "JH003"))


def test_jit_silent_on_good_fixture():
    _, findings = _run_pass(jit_hygiene, "jit_good.py",
                            "reporter_tpu/ops/fixture_good.py")
    assert findings == []


def test_jit_reaches_called_helpers():
    # the while-loop branch lives in helper(), reached only through the
    # jitted entry_calls_helper — cross-function reachability must hold
    sf, findings = _run_pass(jit_hygiene, "jit_bad.py",
                             "reporter_tpu/ops/fixture_bad.py")
    helper_line = next(i for i, ln in
                       enumerate(sf.text.splitlines(), start=1)
                       if "while v > 0" in ln)
    assert any(f.rule == "JH003" and f.line == helper_line
               for f in findings)


# ---- lock discipline -------------------------------------------------------

def test_locks_fire_on_bad_fixture():
    sf, findings = _run_pass(locks, "locks_bad.py",
                             "reporter_tpu/streaming/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("LD001",))


def test_locks_silent_on_good_fixture():
    _, findings = _run_pass(locks, "locks_good.py",
                            "reporter_tpu/streaming/fixture_good.py")
    assert findings == []


# ---- suppressions ----------------------------------------------------------

def test_suppression_comment_silences_rule():
    src = ("def f(rows):\n"
           "    out = []\n"
           "    for r in rows:\n"
           "        out.append({'id': r})  # lint: ignore[HP002]\n"
           "    return out\n")
    import ast
    sf = SourceFile(path="x", relpath="reporter_tpu/matcher/x.py",
                    text=src, tree=ast.parse(src),
                    suppressions=parse_suppressions(src))
    findings = analysis.filter_suppressed(hotpath.run([sf], REPO), [sf])
    assert findings == []
    # without the suppression the same code fires
    bare = src.replace("  # lint: ignore[HP002]", "")
    sf2 = SourceFile(path="x", relpath="reporter_tpu/matcher/x.py",
                     text=bare, tree=ast.parse(bare),
                     suppressions=parse_suppressions(bare))
    assert any(f.rule == "HP002" for f in hotpath.run([sf2], REPO))


# ---- durability ------------------------------------------------------------

_DUR_FIXTURE_CONTRACTS = {
    f"reporter_tpu/streaming/fixture_bad.py::{fn}":
        ("punctuate", "commit_epoch")
    for fn in ("commit_before_ack", "commit_without_ack",
               "missing_commit")}
_DUR_GOOD_CONTRACTS = {
    "reporter_tpu/streaming/fixture_good.py::commit_after_ack":
        ("punctuate", "commit_epoch")}


def test_durability_fires_on_bad_fixture():
    sf = _fixture("durability_bad.py",
                  "reporter_tpu/streaming/fixture_bad.py")
    findings = analysis.filter_suppressed(
        durability.run([sf], REPO, modules=(sf.relpath,),
                       contracts=_DUR_FIXTURE_CONTRACTS), [sf])
    _assert_matches_annotations(sf, findings,
                                ("DUR001", "DUR002", "DUR003", "DUR004"))


def test_durability_silent_on_good_fixture():
    sf = _fixture("durability_good.py",
                  "reporter_tpu/streaming/fixture_good.py")
    findings = durability.run([sf], REPO, modules=(sf.relpath,),
                              contracts=_DUR_GOOD_CONTRACTS)
    assert findings == []


def test_durability_scope_is_declared_module_set():
    # the same bad writes OUTSIDE the durable-module set are not flagged
    sf = _fixture("durability_bad.py", "reporter_tpu/tools/fixture.py")
    findings = durability.run([sf], REPO, contracts={})
    assert findings == []


def test_durability_live_flush_contract_holds():
    """The shipped worker._flush_tiles satisfies the epoch-commit
    ordering, and reordering the marker before the egress is caught —
    the ABI live-pair pattern applied to the CFG contract."""
    live = _read(os.path.join(REPO, "reporter_tpu", "streaming",
                              "worker.py"))
    sf = SourceFile.load(
        os.path.join(REPO, "reporter_tpu", "streaming", "worker.py"),
        REPO)
    assert durability.run([sf], REPO) == []
    # mutate a copy: commit the epoch BEFORE punctuate
    target = "written = self.anonymiser.punctuate()"
    assert target in live, "worker flush drifted; update the injection"
    mutated = live.replace(
        target,
        "self.state.commit_epoch(epoch)\n        " + target, 1)
    import ast
    bad = SourceFile(path="x", relpath="reporter_tpu/streaming/worker.py",
                     text=mutated, tree=ast.parse(mutated),
                     suppressions={})
    findings = durability.run([bad], REPO)
    assert any(f.rule == "DUR004" for f in findings), \
        [f.render() for f in findings]


def test_durability_live_modules_are_clean():
    files = [SourceFile.load(os.path.join(REPO, rel), REPO)
             for rel in registry.DURABLE_MODULES]
    findings = analysis.filter_suppressed(
        durability.run(files, REPO), files)
    assert findings == [], [f.render() for f in findings]


# ---- lock graph ------------------------------------------------------------

def test_lockgraph_fires_on_bad_fixture():
    sf, findings = _run_pass(lockgraph, "lockgraph_bad.py",
                             "reporter_tpu/streaming/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("LD002", "LD003"))


def test_lockgraph_silent_on_good_fixture():
    _, findings = _run_pass(lockgraph, "lockgraph_good.py",
                            "reporter_tpu/streaming/fixture_good.py")
    assert findings == []


def test_lockgraph_native_build_lock_is_the_only_suppression():
    """The live package carries exactly one documented LD003 hold: the
    native once-only build lock (subprocess make + ABI handshake)."""
    files = analysis.collect_py_files(REPO)
    raw = lockgraph.run(files, REPO)
    native = [f for f in raw
              if f.path == "reporter_tpu/native/__init__.py"
              and f.rule == "LD003"]
    assert native, "the build-lock hold disappeared — update the test"
    kept = analysis.filter_suppressed(raw, files)
    assert kept == [], [f.render() for f in kept]


# ---- registry drift --------------------------------------------------------

_FIXTURE_KNOBS = {"REPORTER_TPU_KNOWN": "fixture knob"}
_FIXTURE_METRICS = {"known.metric": "fixture", "family.*": "fixture"}


def _run_registry(name, relpath):
    sf = _fixture(name, relpath)
    findings = analysis.filter_suppressed(
        registry_drift.run([sf], REPO, knobs=_FIXTURE_KNOBS,
                           metrics_reg=_FIXTURE_METRICS,
                           readme_text="", full_scope=False), [sf])
    return sf, findings


def test_registry_drift_fires_on_bad_fixture():
    sf, findings = _run_registry("registry_bad.py",
                                 "reporter_tpu/streaming/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("KN001", "MT001"))


def test_registry_drift_silent_on_good_fixture():
    _, findings = _run_registry("registry_good.py",
                                "reporter_tpu/streaming/fixture_good.py")
    assert findings == []


def test_registry_dead_knob_and_readme_drift_detected():
    """Full-scope reverse directions against the LIVE tree: dropping a
    knob from a registry copy fires KN001 nowhere but KN002+code drift
    where expected, and an unregistered README row fires KN002."""
    files = analysis.collect_py_files(
        REPO, [os.path.join(REPO, "reporter_tpu"),
               os.path.join(REPO, "tools"),
               os.path.join(REPO, "bench.py")])
    readme = _read(os.path.join(REPO, "README.md"))
    # a registered-but-never-mentioned knob is a dead entry (KN001)
    knobs = dict(registry.ENV_KNOBS, REPORTER_TPU_GHOST="never read")
    findings = registry_drift.run(files, REPO, knobs=knobs,
                                  readme_text=readme)
    assert any(f.rule == "KN001" and "REPORTER_TPU_GHOST" in f.message
               for f in findings)
    assert any(f.rule == "KN002" and "REPORTER_TPU_GHOST" in f.message
               for f in findings)
    # dropping a live knob from the registry: its read sites fire KN001
    # and its README row fires KN002
    knobs = dict(registry.ENV_KNOBS)
    del knobs["REPORTER_TPU_FAULTS"]
    findings = registry_drift.run(files, REPO, knobs=knobs,
                                  readme_text=readme)
    assert any(f.rule == "KN001" and "REPORTER_TPU_FAULTS" in f.message
               for f in findings)
    assert any(f.rule == "KN002" and f.path == "README.md"
               and "REPORTER_TPU_FAULTS" in f.message
               for f in findings)


def test_registry_dead_metric_detected():
    files = analysis.collect_py_files(REPO)
    metrics_reg = dict(registry.METRICS, **{"ghost.metric": "dead"})
    findings = registry_drift.run(files, REPO, metrics_reg=metrics_reg)
    assert any(f.rule == "MT002" and "ghost.metric" in f.message
               for f in findings)


def test_registry_unregistered_live_metric_detected():
    """Dropping a metric from a registry copy makes its live call site
    fire MT001 — the two-sided contract on the real tree."""
    files = analysis.collect_py_files(REPO)
    metrics_reg = dict(registry.METRICS)
    del metrics_reg["egress.deadletter"]
    findings = registry_drift.run(files, REPO, metrics_reg=metrics_reg,
                                  full_scope=False)
    assert any(f.rule == "MT001" and "egress.deadletter" in f.message
               and f.path == "reporter_tpu/streaming/anonymiser.py"
               for f in findings)


def test_readme_knob_table_parser_reads_full_names():
    readme = _read(os.path.join(REPO, "README.md"))
    table = registry_drift.parse_readme_knobs(readme)
    # the five knobs PR 6 closed the drift on are all table rows now
    for name in ("REPORTER_TPU_CHAOS_REQUIRE_NATIVE",
                 "REPORTER_TPU_NUM_PROCESSES",
                 "REPORTER_TPU_PLATFORM",
                 "REPORTER_TPU_PROCESS_ID",
                 "REPORTER_TPU_ROUTE_CACHE_PAIRS"):
        assert name in table, f"{name} missing from README's knob table"


# ---- fault coverage --------------------------------------------------------

_FIXTURE_SITES = {"known.site": "fixture"}


def _run_faultcov(name, relpath):
    sf = _fixture(name, relpath)
    findings = analysis.filter_suppressed(
        fault_coverage.run([sf], REPO, sites=_FIXTURE_SITES,
                           full_scope=False), [sf])
    return sf, findings


def test_faultcov_fires_on_bad_fixture():
    sf, findings = _run_faultcov("faultcov_bad.py",
                                 "reporter_tpu/streaming/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("FP001",))


def test_faultcov_silent_on_good_fixture():
    _, findings = _run_faultcov("faultcov_good.py",
                                "reporter_tpu/streaming/fixture_good.py")
    assert findings == []


def test_faultcov_registry_mirrors_known_sites():
    import reporter_tpu.utils.faults as faults_mod
    assert set(registry.FAULT_SITES) == set(faults_mod.KNOWN_SITES)


def test_faultcov_live_drift_and_coverage_detected():
    """Against the LIVE tree: an extra registry site fires FP001 (KNOWN_
    SITES drift) + FP002 (no hook) + FP003 (no coverage); removing a
    real site fires FP001 at its call sites."""
    files = analysis.collect_py_files(REPO)
    sites = dict(registry.FAULT_SITES, **{"ghost.site": "nothing"})
    findings = fault_coverage.run(files, REPO, sites=sites)
    rules = {f.rule for f in findings if "ghost.site" in f.message}
    assert rules == {"FP001", "FP002", "FP003"}, \
        [f.render() for f in findings]
    sites = dict(registry.FAULT_SITES)
    del sites["worker.offer"]
    findings = fault_coverage.run(files, REPO, sites=sites)
    assert any(f.rule == "FP001" and "worker.offer" in f.message
               and f.path == "reporter_tpu/streaming/worker.py"
               for f in findings)


def test_faultcov_every_site_is_exercised():
    """FP003's contract directly: every registered site appears in a
    chaos scenario or a fault test (worker.post_egress was the gap this
    pass surfaced; tests/test_faults.py now pins it)."""
    files = analysis.collect_py_files(REPO)
    findings = fault_coverage.run(files, REPO)
    assert [f for f in findings if f.rule == "FP003"] == [], \
        [f.render() for f in findings]


# ---- tensor contracts ------------------------------------------------------

_TC_FIXTURE_CONTRACTS = {
    "reporter_tpu/ops/fixture_bad.py::contracted": "fixture",
    "reporter_tpu/ops/fixture_good.py::contracted": "fixture"}


def _run_tensor(name, relpath):
    sf = _fixture(name, relpath)
    findings = analysis.filter_suppressed(
        tensorcontract.run([sf], REPO, contracts=_TC_FIXTURE_CONTRACTS,
                           full_scope=False), [sf])
    return sf, findings


def test_tensorcontract_fires_on_bad_fixture():
    sf, findings = _run_tensor("tensorcontract_bad.py",
                               "reporter_tpu/ops/fixture_bad.py")
    _assert_matches_annotations(sf, findings, ("TC002", "TC003", "TC004"))


def test_tensorcontract_silent_on_good_fixture():
    _, findings = _run_tensor("tensorcontract_good.py",
                              "reporter_tpu/ops/fixture_good.py")
    assert findings == []


def test_tensorcontract_live_entries_are_all_contracted():
    """TC002 forward on the live tree: every enumerated jit/pallas entry
    has a KERNEL_CONTRACTS row (the acceptance gate's two-sided half
    that needs no eval harness)."""
    files = analysis.collect_py_files(REPO)
    findings = tensorcontract.run(files, REPO, full_scope=False)
    assert [f for f in findings if f.rule == "TC002"] == [], \
        [f.render() for f in findings]


def test_tensorcontract_signature_drift_detected():
    """Live injection: mutate a fresh-signature copy's output dtype
    (f32 -> f64 widening, the HBM-doubling class) — TC001 fires at the
    kernel's def line with the drift spelled out."""
    import copy
    import json
    with open(os.path.join(REPO, "tools", "kernel_contracts.json"),
              encoding="utf-8") as f:
        committed = json.load(f)
    fresh = copy.deepcopy(committed)
    key = "reporter_tpu/ops/route_relax.py::relax_csr"
    fresh["entries"][key]["cases"][0]["outputs"][0][1] = "float64"
    files = analysis.collect_py_files(REPO)
    findings = tensorcontract.run(files, REPO, signatures=fresh)
    assert any(f.rule == "TC001" and key in f.message
               and "float64" in f.message
               and f.path == "reporter_tpu/ops/route_relax.py"
               for f in findings), [f.render() for f in findings]
    # a dropped output is drift too, not silence
    fresh = copy.deepcopy(committed)
    fresh["entries"][key]["cases"][0]["outputs"].pop()
    findings = tensorcontract.run(files, REPO, signatures=fresh)
    assert any(f.rule == "TC001" and "output count" in f.message
               for f in findings)


def test_kernel_contracts_regen_containment():
    """Seed-containment (the LEDGER.jsonl pattern): every committed
    contract entry is contained in a fresh CPU-only regen, so hand
    edits to tools/kernel_contracts.json cannot drift from the live
    kernels — and the regen traces no entry the file lacks."""
    import json
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    fresh = tensorcontract.compute_signatures(REPO)
    with open(os.path.join(REPO, "tools", "kernel_contracts.json"),
              encoding="utf-8") as f:
        committed = json.load(f)
    assert set(committed["entries"]) == set(fresh["entries"])
    for key, entry in committed["entries"].items():
        diff = tensorcontract._diff_entry(entry, fresh["entries"][key])
        assert diff is None, f"{key}: {diff}"
    assert tensorcontract.LAST_EVAL_SECONDS is not None


# ---- placement -------------------------------------------------------------

_DP_ENTRIES = {"kernel_entry"}


def test_placement_fires_on_bad_fixture():
    sf = _fixture("placement_bad.py",
                  "reporter_tpu/matcher/fixture_bad.py")
    findings = analysis.filter_suppressed(placement.run(
        [sf], REPO,
        lanes=("reporter_tpu/matcher/fixture_bad.py::Lane.stage",),
        sync_points=("reporter_tpu/matcher/fixture_bad.py::Lane.drain",),
        entry_names=_DP_ENTRIES, full_scope=False), [sf])
    _assert_matches_annotations(sf, findings, ("DP001", "DP002", "DP003"))


def test_placement_silent_on_good_fixture():
    sf = _fixture("placement_good.py",
                  "reporter_tpu/matcher/fixture_good.py")
    findings = placement.run(
        [sf], REPO,
        lanes=("reporter_tpu/matcher/fixture_good.py::Lane.stage",),
        sync_points=("reporter_tpu/matcher/fixture_good.py::Lane.drain",),
        entry_names=_DP_ENTRIES, full_scope=False)
    assert findings == []


def test_placement_live_lanes_are_disciplined():
    """The declared lanes materialise only through SYNC_POINTS on the
    live tree — the PR 15 fill_prep tail now routes through
    DeferredRoutes.write_back instead of an inline np.asarray."""
    files = analysis.collect_py_files(REPO)
    findings = analysis.filter_suppressed(
        placement.run(files, REPO), files)
    assert findings == [], [f.render() for f in findings]


def test_placement_undeclared_sync_detected():
    """Live injection (the durability-worker pattern): re-introduce the
    inline materialisation this PR removed from fill_prep's synchronous
    tail — DP001 fires at the real line on the route prep lane."""
    import ast as _ast
    live = _read(os.path.join(REPO, "reporter_tpu", "graph",
                              "route_device.py"))
    target = "DeferredRoutes(route, dev_max, B, T).write_back(out)"
    assert target in live, "fill_prep tail drifted; update the injection"
    mutated = live.replace(
        target, 'out["route_m"][:B, :T - 1] = np.asarray(route)', 1)
    bad = SourceFile(path="x",
                     relpath="reporter_tpu/graph/route_device.py",
                     text=mutated, tree=_ast.parse(mutated),
                     suppressions={})
    files = [bad if sf.relpath == bad.relpath else sf
             for sf in analysis.collect_py_files(REPO)]
    findings = placement.run(files, REPO)
    assert any(f.rule == "DP001" and f.path == bad.relpath
               and "'route'" in f.message for f in findings), \
        [f.render() for f in findings]


# ---- fallback parity -------------------------------------------------------

_FB_FIXTURE_PAIRS = {"covered.circuit": {
    "fault_site": "native.prep", "knob": "REPORTER_TPU_NATIVE",
    "parity_test": "tests/test_faults.py::TestDecodeDomain"}}


def test_fallback_fires_on_bad_fixture():
    sf = _fixture("fallback_bad.py",
                  "reporter_tpu/service/fixture_bad.py")
    findings = analysis.filter_suppressed(
        fallback.run([sf], REPO, pairs=_FB_FIXTURE_PAIRS,
                     full_scope=False), [sf])
    _assert_matches_annotations(sf, findings, ("FB001",))


def test_fallback_silent_on_good_fixture():
    sf = _fixture("fallback_good.py",
                  "reporter_tpu/service/fixture_good.py")
    findings = fallback.run([sf], REPO, pairs=_FB_FIXTURE_PAIRS,
                            full_scope=False)
    assert findings == []


def test_fallback_live_pairs_are_fully_proven():
    """All four dual paths carry full pairs, every parity test resolves,
    and the one pairless breaker (matcher.circuit.assemble — quarantine,
    not a dual path) is a documented suppression."""
    files = analysis.collect_py_files(REPO)
    raw = fallback.run(files, REPO)
    assemble = [f for f in raw if f.rule == "FB001"
                and "matcher.circuit.assemble" in f.message]
    assert assemble, "the assemble suppression disappeared — update"
    kept = analysis.filter_suppressed(raw, files)
    assert kept == [], [f.render() for f in kept]


def test_fallback_missing_leg_detected_at_registry_line():
    """Live injection: drop the kill-switch leg from a FALLBACK_PAIRS
    copy — FB002 fires at the domain's real registry.py line."""
    import copy
    pairs = copy.deepcopy(dict(registry.FALLBACK_PAIRS))
    del pairs["matcher.circuit"]["knob"]
    files = analysis.collect_py_files(REPO)
    findings = fallback.run(files, REPO, pairs=pairs)
    hits = [f for f in findings if f.rule == "FB002"
            and "'knob'" in f.message]
    assert hits, [f.render() for f in findings]
    assert hits[0].path == "reporter_tpu/analysis/registry.py"
    assert hits[0].line > 1  # anchored at the real entry, not a stub


def test_fallback_dropped_pair_detected_at_breaker_site():
    """Drop a whole pair: FB001 fires at the real CircuitBreaker
    construction in matcher.py (the two-sided contract's code half)."""
    pairs = dict(registry.FALLBACK_PAIRS)
    del pairs["matcher.circuit.route"]
    files = analysis.collect_py_files(REPO)
    findings = analysis.filter_suppressed(
        fallback.run(files, REPO, pairs=pairs), files)
    assert any(f.rule == "FB001"
               and f.path == "reporter_tpu/matcher/matcher.py"
               and "matcher.circuit.route" in f.message
               for f in findings), [f.render() for f in findings]


def test_fallback_dangling_parity_test_detected():
    import copy
    pairs = copy.deepcopy(dict(registry.FALLBACK_PAIRS))
    pairs["wire.circuit"]["parity_test"] = \
        "tests/test_report_writer.py::test_gone_forever"
    files = analysis.collect_py_files(REPO)
    findings = fallback.run(files, REPO, pairs=pairs)
    assert any(f.rule == "FB003" and "test_gone_forever" in f.message
               for f in findings), [f.render() for f in findings]
    pairs["wire.circuit"]["parity_test"] = "tests/test_nowhere.py::t"
    findings = fallback.run(files, REPO, pairs=pairs)
    assert any(f.rule == "FB003" and "does not exist" in f.message
               for f in findings)


# ---- ABI cross-check -------------------------------------------------------

def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_abi_good_fixture_pair_is_clean():
    findings = abi.check(_read(os.path.join(FIXTURES, "abi_good.cpp")),
                         _read(os.path.join(FIXTURES, "abi_good.py")),
                         "abi_good.cpp", "abi_good.py")
    assert findings == []


def test_abi_bad_fixture_catches_every_drift_class():
    findings = abi.check(_read(os.path.join(FIXTURES, "abi_good.cpp")),
                         _read(os.path.join(FIXTURES, "abi_bad.py")),
                         "abi_good.cpp", "abi_bad.py")
    rules = {f.rule for f in findings}
    assert rules == {"ABI001", "ABI002", "ABI003", "ABI004", "ABI005"}


def test_abi_live_pair_validates_at_version_14():
    # ABI 14: rt_prepare_batch gains prune_margin/skip_routes scalars and
    # the dt output tensor (ISSUE 16) — same export set, new signature
    cpp = _read(LIVE_CPP)
    exports, version = abi.parse_cpp(cpp)
    assert version == 14
    assert "rt_prepare_batch" in exports and "rt_assemble_batch" in exports
    # the ABI-13 route-memo profile surface (export + pre-warm)
    assert "rt_route_memo_export" in exports \
        and "rt_route_memo_warm" in exports
    # the ABI-12 wire writers are part of the checked surface
    assert "rt_report_json" in exports \
        and "rt_report_json_batch" in exports \
        and "rt_render_segments_json" in exports
    findings = abi.check(cpp, _read(LIVE_PY))
    assert findings == [], [f.render() for f in findings]


def test_abi_injected_argtypes_mismatch_is_caught(tmp_path):
    """Satellite contract: inject a deliberate argtypes mismatch into a
    fixture COPY of the live binding and assert the checker fails it."""
    live = _read(LIVE_PY)
    # rt_route_matrices binds T as c_int64; narrow it to c_int32
    target = ("lib.rt_route_matrices.argtypes = [\n"
              "            ctypes.c_void_p, ctypes.c_int64,")
    assert target in live, "live binding drifted; update the injection"
    mutated = live.replace(
        target, target.replace("c_int64", "c_int32"), 1)
    bad_py = tmp_path / "native_init_mutated.py"
    bad_py.write_text(mutated, encoding="utf-8")
    findings = abi.run_paths(LIVE_CPP, str(bad_py),
                             abi.DEFAULT_CPP, "native_init_mutated.py")
    assert any(f.rule == "ABI003" and "rt_route_matrices" in f.message
               and "i32" in f.message for f in findings), \
        [f.render() for f in findings]


def test_abi_version_bump_is_caught(tmp_path):
    live = _read(LIVE_PY)
    mutated = re.sub(r"^ABI_VERSION = \d+", "ABI_VERSION = 999", live,
                     count=1, flags=re.MULTILINE)
    assert mutated != live
    bad_py = tmp_path / "native_init_ver.py"
    bad_py.write_text(mutated, encoding="utf-8")
    findings = abi.run_paths(LIVE_CPP, str(bad_py),
                             abi.DEFAULT_CPP, "native_init_ver.py")
    assert any(f.rule == "ABI004" for f in findings)


# ---- the driver ------------------------------------------------------------

def _lint(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO)


def test_repo_wide_run_is_clean_against_committed_baseline():
    """Acceptance gate: `python tools/lint.py` exits 0 — no new findings,
    no stale baseline entries."""
    proc = _lint()
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_abi_only_guard_passes_on_live_pair_and_fails_on_mismatch(tmp_path):
    assert _lint("--abi-only").returncode == 0
    mutated = _read(LIVE_PY).replace("ctypes.c_double, c_f32p]",
                                     "ctypes.c_double, c_f64p]", 1)
    bad_py = tmp_path / "native_guard.py"
    bad_py.write_text(mutated, encoding="utf-8")
    proc = _lint("--abi-only", "--abi-py", str(bad_py))
    assert proc.returncode == 1
    assert "ABI003" in proc.stdout


def test_stale_baseline_entry_fails_the_run(tmp_path):
    stale = tmp_path / "baseline.txt"
    stale.write_text("reporter_tpu/matcher/matcher.py:1: HP001 ghost\n",
                     encoding="utf-8")
    proc = _lint("--baseline", str(stale))
    assert proc.returncode == 1
    assert "stale baseline entry" in proc.stdout


def test_partial_run_does_not_report_unrelated_baseline_as_stale(tmp_path):
    # an entry for a file OUTSIDE the requested paths legitimately does
    # not fire on a partial run — it must not be called stale
    base = tmp_path / "baseline.txt"
    base.write_text("reporter_tpu/service/report.py:1: HP001 ghost\n",
                    encoding="utf-8")
    proc = _lint("reporter_tpu/matcher/matcher.py",
                 "--baseline", str(base))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # and --write-baseline refuses a partial run outright
    proc = _lint("reporter_tpu/matcher/matcher.py", "--write-baseline",
                 "--baseline", str(base))
    assert proc.returncode == 2


def test_jit_positional_dtype_not_flagged():
    import ast
    src = ("import jax\nimport jax.numpy as jnp\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    a = jnp.arange(0, 10, 1, jnp.int32)\n"
           "    b = jnp.zeros(x.shape, jnp.float32)\n"
           "    c = jnp.arange(10)\n"                      # no dtype: flag
           "    return a + b + c\n")
    sf = SourceFile(path="x", relpath="reporter_tpu/ops/x.py", text=src,
                    tree=ast.parse(src), suppressions={})
    findings = jit_hygiene.run([sf], REPO)
    assert [f.line for f in findings if f.rule == "JH002"] == [7]


def test_abi_parses_plain_int_and_typed_pointer_returns():
    cpp = ('extern "C" {\n'
           "int32_t rt_abi_version(void) { return 1; }\n"
           "int rt_plain(int64_t n) { return 0; }\n"
           "double* rt_buf(void* h) { return 0; }\n"
           "}\n")
    exports, version = abi.parse_cpp(cpp)
    assert version == 1
    assert exports["rt_plain"] == (("val", "i32"), [("val", "i64")])
    assert exports["rt_buf"] == (("ptr", "f64"), [("ptr", "void")])
    # an unbound export of either shape raises ABI001, not silence
    py = "ABI_VERSION = 1\n"
    rules = {f.rule for f in abi.check(cpp, py, "c.cpp", "b.py")}
    assert "ABI001" in rules


def test_list_rules_covers_all_passes():
    proc = _lint("--list-rules")
    assert proc.returncode == 0
    for rule in ("HP001", "HP002", "HP003", "JH001", "JH002", "JH003",
                 "ABI001", "ABI004", "LD001", "LD002", "LD003",
                 "DUR001", "DUR002", "DUR003", "DUR004",
                 "KN001", "KN002", "MT001", "MT002",
                 "FP001", "FP002", "FP003",
                 "TC001", "TC002", "TC003", "TC004",
                 "DP001", "DP002", "DP003",
                 "FB001", "FB002", "FB003"):
        assert rule in proc.stdout


def test_contracts_only_guard_is_clean_and_catches_drift(tmp_path):
    """--contracts-only passes on the live tree and fails loudly when
    README drops a knob row (the five-knob drift class, kept closed)."""
    assert _lint("--contracts-only").returncode == 0
    readme_path = os.path.join(REPO, "README.md")
    readme = _read(readme_path)
    target = "| `REPORTER_TPU_VIRTUAL_DEVICES` |"
    assert target in readme, "README knob table drifted; update the test"
    # simulate the drift in-process (the driver reads the real README,
    # so exercise the pass directly on a mutated copy)
    files = analysis.collect_py_files(
        REPO, [os.path.join(REPO, "reporter_tpu"),
               os.path.join(REPO, "tools"),
               os.path.join(REPO, "bench.py")])
    mutated = "\n".join(ln for ln in readme.splitlines()
                        if not ln.startswith(target))
    findings = registry_drift.run(files, REPO, readme_text=mutated)
    assert any(f.rule == "KN002"
               and "REPORTER_TPU_VIRTUAL_DEVICES" in f.message
               for f in findings)


def test_tensors_only_guard_is_clean_and_reports_eval_time():
    """--tensors-only exits 0 on the live tree and prints the eval_shape
    harness wall time (the CI budget guard's visibility hook)."""
    proc = _lint("--tensors-only")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "eval_shape harness" in proc.stdout


def test_partial_run_skips_whole_package_contract_directions():
    # a single-file run must not call registry entries "dead" just
    # because their users are outside the requested paths
    proc = _lint("reporter_tpu/matcher/matcher.py")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def _readme_rule_ids():
    """Every rule id documented in README's Static-analysis table,
    ranges expanded (``ABI001-005`` -> ABI001..ABI005)."""
    readme = _read(os.path.join(REPO, "README.md"))
    ids = set()
    in_table = False
    for line in readme.splitlines():
        if line.startswith("| rule |"):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                break
            cell = line.split("|")[1].strip()
            m = re.match(r"^([A-Z]{2,3})(\d{3})(?:-(?:[A-Z]{2,3})?(\d{3}))?$",
                         cell)
            if not m:
                continue
            prefix, lo, hi = m.group(1), int(m.group(2)), m.group(3)
            for n in range(lo, (int(hi) if hi else lo) + 1):
                ids.add(f"{prefix}{n:03d}")
    return ids


def test_readme_rule_table_matches_the_suite():
    """lint_fixtures self-check (ISSUE 6 satellite): every rule id
    documented in README exists in the suite, and every implemented
    rule is documented."""
    documented = _readme_rule_ids()
    implemented = set(analysis.ALL_RULES)
    assert documented == implemented, (
        f"README-only: {sorted(documented - implemented)}; "
        f"undocumented: {sorted(implemented - documented)}")


def test_every_rule_id_has_a_fixture_test():
    """Every non-ABI rule id is exercised by a bad fixture annotation
    (the ABI rules pin through the fixture .cpp/.py pair instead)."""
    annotated = set()
    for name in os.listdir(FIXTURES):
        if not name.endswith(".py"):
            continue
        text = _read(os.path.join(FIXTURES, name))
        annotated.update(re.findall(r"#\s*([A-Z]{2,3}\d{3})(?:\s*\(x\d+\))?:",
                                    text))
    # whole-package reverse directions (dead entries, README drift,
    # coverage) are pinned by the live-tree tests above, not fixtures
    full_scope_only = {"KN002", "MT002", "FP002", "FP003",
                       "TC001", "FB002", "FB003"}
    # the RC rules are RUNTIME findings (the lock witness / guarded
    # audit, ISSUE 10): they pin through tests/test_racecheck.py
    # driving real threads, not through AST fixtures
    runtime = set(analysis.racecheck.RULES)
    missing = {r for r in analysis.ALL_RULES
               if not r.startswith("ABI")} \
        - full_scope_only - runtime - annotated
    assert missing == set(), f"rules with no bad-fixture line: {missing}"
