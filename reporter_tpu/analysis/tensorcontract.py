"""Tensor-contract pass (TC rules): kernel shape/dtype signatures.

PRs 12 and 15 moved decode and route costs onto the device, so the
system's throughput now hangs on invariants no syntactic pass can see: a
silent f32->f64 widening doubles HBM and forks the compile cache, a new
shape axis retraces every bucket, and a dropped output breaks every
caller at dispatch time, not at lint time. This pass closes the loop
with an *abstract-evaluation harness*: ``jax.eval_shape`` drives every
registered jit entry point across representative bucket-ladder shapes —
pure tracing, no device, no FLOPs — and the resulting signatures are
diffed against the committed ``tools/kernel_contracts.json``.

TC001  signature drift: the committed contract and the freshly traced
       signature disagree (dtype widening, a new shape axis, an output
       count change, a static_argnames change), or the contract file
       lags the harness (an entry added/removed without a regen).
TC002  two-sided jit-entry coverage: every entry the jit_hygiene
       enumerator finds must be a ``registry.KERNEL_CONTRACTS`` key and
       vice versa — an uncontracted kernel is invisible to TC001, a
       dead contract is documentation rot.
TC003  weak-typed Python scalar promotion inside jit-reachable code:
       a ``jnp.where`` whose *both* value branches are bare Python
       scalars (or module constants bound to them) has no array operand
       to inherit a dtype from — the result follows the x64 flag, and
       everything downstream promotes with it. One weak branch against
       an array operand is the codebase's sanctioned idiom (the scalar
       adopts the array dtype) and is not flagged.
TC004  ``static_argnames`` naming an array-valued argument: a static
       that is subscripted or carries array attributes inside the
       region is hashed per call (cache storm) or is simply a typo
       naming no parameter at all.

Regen workflow: ``python -m reporter_tpu.analysis.tensorcontract
--write`` rewrites tools/kernel_contracts.json from the live kernels;
the seed-containment test (tests/test_lint.py) pins the committed file
to stay a subset of a fresh regen, so hand edits cannot drift.

Everything except the TC001 harness is stdlib-ast only; jax is imported
lazily inside :func:`compute_signatures` (full-scope runs only), under
``JAX_PLATFORMS=cpu`` by default so the lint stage needs no accelerator.
"""
from __future__ import annotations

import ast
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import registry
from .core import Finding, SourceFile, dotted
from .jit_hygiene import (_SHAPE_ATTRS, _Module, _collect_regions,
                          _find_entries)

RULES = {
    "TC001": "kernel signature drift against tools/kernel_contracts.json",
    "TC002": "jit entry <-> registry.KERNEL_CONTRACTS coverage gap",
    "TC003": "weak-typed Python scalar promotion in a jit region",
    "TC004": "static_argnames naming an array-valued (or absent) argument",
}

REGISTRY_REL = "reporter_tpu/analysis/registry.py"
CONTRACTS_REL = "tools/kernel_contracts.json"

#: wall seconds of the last eval_shape harness run (None until one runs)
#: — the lint driver prints this so CI can watch the budget.
LAST_EVAL_SECONDS: Optional[float] = None

# ---- the eval_shape harness ------------------------------------------------

#: representative dimensions: candidate fan-out K, node/edge/source
#: counts sized so a full trace stays milliseconds (shapes only — the
#: harness never materialises an array)
_DIMS = {"B": 8, "K": 4, "N": 96, "E": 256, "S": 16}
#: representative rungs of the time-axis bucket ladder
_BUCKETS = (64, 256)

_F32, _I32, _BOOL = "float32", "int32", "bool"


def _decode_cases(d, extra_statics=None):
    """(dist_m, valid, route_m, gc_m, case, sigma, beta) per bucket —
    the shared decode-kernel signature (hmm / assoc / pallas)."""
    B, K = d["B"], d["K"]
    out = []
    for T in _BUCKETS:
        out.append(([((B, T, K), _F32), ((B, T, K), _BOOL),
                     ((B, T - 1, K, K), _F32), ((B, T - 1), _F32),
                     ((B, T), _I32), ((), _F32), ((), _F32)],
                    dict(extra_statics or {})))
    return out


def _incremental_cases(d):
    """(dist_m, valid, route_m, gc_m, case, live, prev_scores, sigma,
    beta) — R = 4 appended kept points for N carried traces (N plays
    the batch role B plays in the windowed decode)."""
    N, K, R = d["B"], d["K"], 4
    return [([((R, N, K), _F32), ((R, N, K), _BOOL), ((R, N, K, K), _F32),
              ((R, N), _F32), ((R, N), _I32), ((R, N), _BOOL),
              ((N, K), _F32), ((), _F32), ((), _F32)], {})]


def _relax_cases(d):
    E, S, N = d["E"], d["S"], d["N"]
    return [([((E,), _I32), ((E,), _I32), ((E,), _F32), ((E,), _F32),
              ((S,), _I32), ((), _F32)],
             {"n_nodes": N, "max_iters": 64})]


def _pair_cases(d):
    B, K, E, S, N = d["B"], d["K"], d["E"], d["S"], d["N"]
    out = []
    for T in _BUCKETS:
        out.append(([((B, T, K), _I32), ((B, T, K), _F32), ((B,), _I32),
                     ((B, T - 1), _F32), ((B, T - 1), _F32),
                     ((S, N), _F32), ((S, N), _F32), ((N,), _I32),
                     ((E,), _I32), ((E,), _I32), ((E,), _F32),
                     ((E,), _F32), ((E,), _F32), ((E,), _F32),
                     ((), _F32), ((), _F32)], {}))
    return out


def _packed_cases(d):
    B, K, E, S, N = d["B"], d["K"], d["E"], d["S"], d["N"]
    out = []
    for T in _BUCKETS:
        btk, bt1 = B * T * K, B * (T - 1)
        out.append(([((btk + B + N,), _I32), ((btk + 2 * bt1 + 2,), _F32),
                     ((S, N), _F32), ((S, N), _F32), ((), _I32),
                     ((B, T - 1, K, K), _F32),
                     ((E,), _I32), ((E,), _I32), ((E,), _F32),
                     ((E,), _F32), ((E,), _F32), ((E,), _F32)],
                    {"B": B, "T": T, "K": K, "N": N}))
    return out


#: contract key -> case builder. Keys absent here (the pallas kernel
#: body, the sharded wrappers) are TC002-covered but carry no JSON
#: cases — their signatures are owned by the entries that call them.
_EVAL_SPECS = {
    "reporter_tpu/ops/route_relax.py::relax_csr": _relax_cases,
    "reporter_tpu/ops/route_relax.py::pair_costs": _pair_cases,
    "reporter_tpu/ops/route_relax.py::pair_costs_packed": _packed_cases,
    "reporter_tpu/ops/assoc_viterbi.py::viterbi_assoc_batch":
        _decode_cases,
    "reporter_tpu/ops/pallas_viterbi.py::viterbi_pallas_batch":
        lambda d: _decode_cases(d, {"interpret": True}),
    "reporter_tpu/matcher/hmm.py::viterbi_decode_batch": _decode_cases,
    "reporter_tpu/ops/incremental.py::incremental_step_batch":
        _incremental_cases,
}


def compute_signatures(repo_root: Optional[str] = None) -> dict:
    """Trace every spec'd kernel with jax.eval_shape and return the
    signature table (the exact structure kernel_contracts.json holds).
    CPU-only safe: abstract evaluation allocates nothing and needs no
    device; JAX_PLATFORMS defaults to cpu unless the caller pinned it."""
    global LAST_EVAL_SECONDS
    t0 = time.monotonic()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools
    import importlib

    import jax
    import numpy as np

    entries: Dict[str, dict] = {}
    for key in sorted(_EVAL_SPECS):
        relpath, fname = key.split("::")
        modname = relpath[:-3].replace("/", ".")
        fn = getattr(importlib.import_module(modname), fname)
        cases = []
        static_names: Set[str] = set()
        for inputs, statics in _EVAL_SPECS[key](_DIMS):
            static_names |= set(statics)
            args = [jax.ShapeDtypeStruct(shape, np.dtype(dt))
                    for shape, dt in inputs]
            out = jax.eval_shape(functools.partial(fn, **statics), *args)
            leaves = jax.tree_util.tree_leaves(out)
            cases.append({
                "statics": {k: statics[k] for k in sorted(statics)},
                "inputs": [[list(s), d] for s, d in inputs],
                "outputs": [[list(l.shape), str(l.dtype)] for l in leaves],
            })
        entries[key] = {"static_argnames": sorted(static_names),
                        "cases": cases}
    LAST_EVAL_SECONDS = time.monotonic() - t0
    return {"version": 1, "dims": dict(_DIMS),
            "buckets": list(_BUCKETS), "entries": entries}


def _diff_entry(committed: dict, fresh: dict) -> Optional[str]:
    """First human-readable difference between two contract entries."""
    if committed.get("static_argnames") != fresh.get("static_argnames"):
        return (f"static_argnames {committed.get('static_argnames')} != "
                f"traced {fresh.get('static_argnames')}")
    cc, fc = committed.get("cases", []), fresh.get("cases", [])
    if len(cc) != len(fc):
        return f"{len(cc)} contracted case(s) != {len(fc)} traced"
    for i, (c, f) in enumerate(zip(cc, fc)):
        for side in ("statics", "inputs"):
            if c.get(side) != f.get(side):
                return f"case {i} {side}: {c.get(side)} != {f.get(side)}"
        co, fo = c.get("outputs", []), f.get("outputs", [])
        if len(co) != len(fo):
            return (f"case {i}: output count {len(co)} contracted != "
                    f"{len(fo)} traced")
        for j, (a, b) in enumerate(zip(co, fo)):
            if a != b:
                return (f"case {i} output {j}: contracted "
                        f"shape={a[0]} dtype={a[1]}, traced "
                        f"shape={b[0]} dtype={b[1]}")
    return None


# ---- AST side (TC002-004) --------------------------------------------------

def _registry_lines(repo_root: str) -> Dict[str, int]:
    path = os.path.join(repo_root, REGISTRY_REL)
    out: Dict[str, int] = {}
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return out
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            out.setdefault(node.value, node.lineno)
    return out


def _def_line(sf: SourceFile, fname: str) -> Optional[int]:
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == fname:
            return node.lineno
    return None


def _module_weak_consts(tree: ast.AST) -> Set[str]:
    """Module-level names bound to bare Python numeric literals (the
    ``NEG_INF = -1.0e30`` idiom) — weak-typed wherever they are used."""
    out: Set[str] = set()
    for node in getattr(tree, "body", []):
        if not (isinstance(node, ast.Assign)
                and all(isinstance(t, ast.Name) for t in node.targets)):
            continue
        v = node.value
        if isinstance(v, ast.UnaryOp):
            v = v.operand
        if isinstance(v, ast.Constant) \
                and isinstance(v.value, (int, float)) \
                and not isinstance(v.value, bool):
            out.update(t.id for t in node.targets)
    return out


def _is_weak(node: ast.AST, consts: Set[str]) -> bool:
    if isinstance(node, ast.UnaryOp):
        node = node.operand
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool)
    return isinstance(node, ast.Name) and node.id in consts


class _RegionScan(ast.NodeVisitor):
    """TC003 + TC004 array-usage over one jit region's subtree."""

    def __init__(self, mod: _Module, statics: Set[str],
                 consts: Set[str]):
        self.mod = mod
        self.statics = statics
        self.consts = consts
        self.jnp_roots = mod.alias_roots("jax.numpy")
        self.findings: List[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        d = dotted(node.func)
        if d is not None and d.split(".")[-1] == "where" \
                and d.split(".")[0] in self.jnp_roots \
                and len(node.args) == 3 \
                and _is_weak(node.args[1], self.consts) \
                and _is_weak(node.args[2], self.consts):
            self.findings.append(Finding(
                self.mod.sf.relpath, node.lineno, "TC003",
                "jnp.where with both branches weak Python scalars — no "
                "array operand pins the dtype, so the result follows "
                "the x64 flag and widens everything downstream; wrap "
                "one branch in an explicit jnp dtype"))
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if isinstance(node.value, ast.Name) \
                and node.value.id in self.statics:
            self.findings.append(Finding(
                self.mod.sf.relpath, node.lineno, "TC004",
                f"static argument {node.value.id!r} is subscripted like "
                "an array — static_argnames hashes it per call (cache "
                "storm) and concretises it at trace time"))
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr in _SHAPE_ATTRS and isinstance(node.value, ast.Name) \
                and node.value.id in self.statics:
            self.findings.append(Finding(
                self.mod.sf.relpath, node.lineno, "TC004",
                f"static argument {node.value.id!r} carries array "
                f"attribute .{node.attr} — it is array-valued; drop it "
                "from static_argnames"))
        self.generic_visit(node)


def run(files: Sequence[SourceFile], repo_root: str,
        contracts: Optional[Dict[str, str]] = None,
        signatures: Optional[dict] = None,
        contracts_path: Optional[str] = None,
        full_scope: bool = True) -> List[Finding]:
    """``full_scope=False`` (partial/fixture runs) checks only the
    code->registry TC002 direction plus TC003/TC004 on the given files —
    the reverse coverage and the TC001 eval harness need the whole
    package (and jax) in view. ``signatures`` injects a pre-computed (or
    deliberately mutated) fresh signature table for tests."""
    contracts = dict(registry.KERNEL_CONTRACTS
                     if contracts is None else contracts)
    findings: List[Finding] = []
    by_rel = {sf.relpath: sf for sf in files}
    reg_lines = _registry_lines(repo_root)

    # -- TC002 forward: every enumerated jit entry is contracted ----------
    enumerated: Dict[str, Set[str]] = {}
    for sf in files:
        mod = _Module(sf)
        for fname, statics in _find_entries(mod).items():
            key = f"{sf.relpath}::{fname}"
            enumerated[key] = statics
            if key not in contracts:
                findings.append(Finding(
                    sf.relpath, _def_line(sf, fname) or 1, "TC002",
                    f"jit entry {key} is not in registry."
                    "KERNEL_CONTRACTS — an uncontracted kernel is "
                    "invisible to the signature diff (TC001)"))

    # -- TC004: statics naming no parameter -------------------------------
    for key, statics in enumerated.items():
        relpath, fname = key.split("::")
        sf = by_rel.get(relpath)
        if sf is None or not statics:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == fname:
                a = node.args
                params = {p.arg for p in (*a.posonlyargs, *a.args,
                                          *a.kwonlyargs)}
                for name in sorted(statics - params):
                    findings.append(Finding(
                        relpath, node.lineno, "TC004",
                        f"static_argnames names {name!r} which is not a "
                        f"parameter of {fname}() — every call re-traces"))
                break

    # -- TC003 / TC004 array-usage over the jit regions --------------------
    consts_by_mod = {sf.relpath: _module_weak_consts(sf.tree)
                     for sf in files}
    for mod, func, statics in _collect_regions(files):
        scan = _RegionScan(mod, statics,
                           consts_by_mod.get(mod.sf.relpath, set()))
        scan.visit(func)
        findings.extend(scan.findings)

    if not full_scope:
        return findings

    # -- TC002 reverse: every contract key has a jit entry -----------------
    for key in sorted(set(contracts) - set(enumerated)):
        findings.append(Finding(
            REGISTRY_REL, reg_lines.get(key, 1), "TC002",
            f"KERNEL_CONTRACTS entry {key} matches no jit entry point "
            "— the kernel it contracts is gone (or renamed)"))

    # -- TC001: the abstract-evaluation diff -------------------------------
    path = contracts_path or os.path.join(repo_root, CONTRACTS_REL)
    try:
        with open(path, encoding="utf-8") as f:
            committed = json.load(f)
    except (OSError, ValueError):
        findings.append(Finding(
            CONTRACTS_REL, 1, "TC001",
            "committed contract file missing or unparseable — run "
            "python -m reporter_tpu.analysis.tensorcontract --write"))
        return findings
    fresh = compute_signatures(repo_root) if signatures is None \
        else signatures
    com_e = committed.get("entries", {})
    fre_e = fresh.get("entries", {})
    for key in sorted(set(com_e) | set(fre_e)):
        relpath, fname = key.split("::")
        sf = by_rel.get(relpath)
        line = (_def_line(sf, fname) if sf is not None else None) or 1
        if key not in fre_e:
            findings.append(Finding(
                relpath if sf is not None else CONTRACTS_REL, line,
                "TC001",
                f"contract entry {key} is no longer traced by the "
                "harness — regenerate tools/kernel_contracts.json"))
            continue
        if key not in com_e:
            findings.append(Finding(
                relpath if sf is not None else CONTRACTS_REL, line,
                "TC001",
                f"kernel {key} is traced by the harness but absent "
                "from the committed contracts — regenerate tools/"
                "kernel_contracts.json"))
            continue
        diff = _diff_entry(com_e[key], fre_e[key])
        if diff is not None:
            findings.append(Finding(
                relpath if sf is not None else CONTRACTS_REL, line,
                "TC001", f"signature drift for {key}: {diff}"))
        # the declared static set is part of the signature
        if key in enumerated and sorted(enumerated[key]) \
                != com_e[key].get("static_argnames", []):
            findings.append(Finding(
                relpath if sf is not None else CONTRACTS_REL, line,
                "TC001",
                f"static_argnames drift for {key}: declared "
                f"{sorted(enumerated[key])}, contracted "
                f"{com_e[key].get('static_argnames', [])}"))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m reporter_tpu.analysis.tensorcontract --write`` —
    regenerate the committed contract file from the live kernels."""
    import argparse
    parser = argparse.ArgumentParser(prog="tensorcontract")
    parser.add_argument("--write", action="store_true",
                        help="rewrite tools/kernel_contracts.json")
    parser.add_argument("--out", default=None,
                        help="override the output path")
    args = parser.parse_args(argv)
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sig = compute_signatures(repo_root)
    text = json.dumps(sig, indent=2, sort_keys=True) + "\n"
    if args.write or args.out:
        out = args.out or os.path.join(repo_root, CONTRACTS_REL)
        with open(out, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"wrote {len(sig['entries'])} contract entr(y/ies) to "
              f"{out} ({LAST_EVAL_SECONDS:.1f}s)")
    else:
        print(text, end="")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
