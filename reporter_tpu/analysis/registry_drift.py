"""Registry-drift pass (KN/MT rules): knobs and metric names, two-sided.

The ``REPORTER_TPU_*`` env surface and the metric names on /stats are
operator API — a knob that README doesn't document is undiscoverable, a
documented knob the code no longer reads is a silent no-op at 3am, and
a renamed metric breaks every dashboard grepping the old name. Five
knobs had already drifted out of README's table when this pass landed.

All checks are TWO-SIDED against :mod:`registry` (the single source of
truth) so the lists can neither rot nor bloat:

KN001  knob drift between the code and the registry: a
       ``REPORTER_TPU_*`` name mentioned in code (any Python string
       constant, or the C++ runtime's ``getenv``) but missing from
       ``registry.ENV_KNOBS`` — or a registered knob nothing reads.
KN002  knob drift between the registry and README's knob table: a
       registered knob with no table row, or a table row for an
       unregistered knob. Rows use FULL variable names (the pre-PR 6
       ``_TRIES``-style shorthand is exactly how five knobs vanished).
MT001  a metric name passed to the metrics layer (``count``/``timer``/
       ``observe`` on a metrics registry) that no registry entry
       covers. Literal names must match an exact entry or a ``prefix.*``
       pattern; f-strings with a static prefix must match a pattern.
       Names that are dynamic from the first character (the circuit
       breaker's ``f"{self.name}.opened"``) are unresolvable and
       skipped — register the instantiated family as a pattern.
       A ``timer(name, cpu=True)`` site also records ``<name>.cpu``
       and ``<name>.cpu_wall``.
MT002  a dead exact registry entry: no string literal anywhere in the
       scanned code matches it. Pattern entries are exempt — they exist
       precisely because their call sites are dynamic.

The registry and this package are excluded from the code scans (the
registry must not witness itself).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import registry
from .core import Finding, SourceFile

RULES = {
    "KN001": "env knob drift between the code and the registry",
    "KN002": "env knob drift between the registry and README's table",
    "MT001": "metric name not covered by the registry",
    "MT002": "dead registry metric entry (no call site or literal)",
}

_KNOB_RE = re.compile(r"^REPORTER_TPU_[A-Z0-9_]+$")
_KNOB_TEXT_RE = re.compile(r"REPORTER_TPU_[A-Z0-9_]+")
_METRIC_SINKS = frozenset({"count", "timer", "observe"})
_METRIC_BASES = frozenset({"metrics", "registry", "_registry", "default",
                           "reg"})
#: package paths excluded from the code-side scans: the registry must
#: not be its own evidence, and fixtures aren't product code.
_SELF = "reporter_tpu/analysis/"
#: analysis/ modules that ARE product code (the runtime concurrency
#: witness emits real metrics/knob reads) — exempt from the self-skip.
_RUNTIME_IN_SELF = ("reporter_tpu/analysis/racecheck.py",)


def _self_excluded(relpath: str) -> bool:
    return relpath.startswith(_SELF) and relpath not in _RUNTIME_IN_SELF

README_KNOB_HEADER = "## Configuration knobs"


def _knob_mentions(files: Sequence[SourceFile]
                   ) -> Dict[str, Tuple[str, int]]:
    """{knob name: (relpath, line) of one mention} over every Python
    string constant in the scanned files (reads, writes, ENV_*
    constants — a mention is a mention)."""
    out: Dict[str, Tuple[str, int]] = {}
    for sf in files:
        if _self_excluded(sf.relpath):
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and _KNOB_RE.match(node.value):
                out.setdefault(node.value,
                               (sf.relpath, node.lineno))
    return out


def _cpp_knob_mentions(repo_root: str) -> Set[str]:
    """Knob names the C++ runtime reads (getenv in native/src)."""
    src_dir = os.path.join(repo_root, "reporter_tpu", "native", "src")
    found: Set[str] = set()
    try:
        names = sorted(os.listdir(src_dir))
    except OSError:
        return found
    for name in names:
        if not name.endswith((".cpp", ".cc", ".h", ".hpp")):
            continue
        try:
            with open(os.path.join(src_dir, name),
                      encoding="utf-8") as f:
                found.update(_KNOB_TEXT_RE.findall(f.read()))
        except OSError:
            continue
    return found


def parse_readme_knobs(readme_text: str) -> Dict[str, int]:
    """{knob name: line} from README's knob-table rows (lines starting
    with ``|`` inside the "Configuration knobs" section)."""
    out: Dict[str, int] = {}
    in_section = False
    for i, line in enumerate(readme_text.splitlines(), start=1):
        if line.startswith("## "):
            in_section = line.startswith(README_KNOB_HEADER)
            continue
        if in_section and line.lstrip().startswith("|"):
            for name in _KNOB_TEXT_RE.findall(line):
                out.setdefault(name, i)
    return out


# ---- metric call-site extraction -------------------------------------------

def _metric_name_glob(node: ast.AST) -> Optional[str]:
    """A metric-name argument as a match glob: literal strings verbatim,
    f-strings with each dynamic field collapsed to ``*`` (only when the
    leading part is static); None = unresolvable (skipped)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        if not parts or parts[0] == "*":
            return None  # dynamic from the first char: unresolvable
        glob = "".join(parts)
        while "**" in glob:
            glob = glob.replace("**", "*")
        return glob
    return None


def _metric_sites(files: Sequence[SourceFile]
                  ) -> List[Tuple[str, int, str]]:
    """(relpath, line, name-glob) for every resolvable metric-name
    argument at a metrics-layer call site."""
    out: List[Tuple[str, int, str]] = []
    for sf in files:
        if _self_excluded(sf.relpath):
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_SINKS):
                continue
            base = node.func.value
            base_name = base.attr if isinstance(base, ast.Attribute) \
                else base.id if isinstance(base, ast.Name) else None
            if base_name not in _METRIC_BASES:
                continue
            if not node.args:
                continue
            glob = _metric_name_glob(node.args[0])
            if glob is None:
                continue
            out.append((sf.relpath, node.lineno, glob))
            if node.func.attr == "timer" and any(
                    kw.arg == "cpu" and isinstance(kw.value, ast.Constant)
                    and kw.value.value for kw in node.keywords):
                out += [(sf.relpath, node.lineno, glob + suffix)
                        for suffix in (".cpu", ".cpu_wall")]
    return out


def _covered(glob: str, metrics_reg: Dict[str, str]) -> bool:
    """Is a call-site name glob covered by the registry? A literal name
    must equal an exact entry or extend a ``prefix.*`` pattern (a
    truncated literal that merely prefixes a pattern is a typo, not
    covered); an f-string glob's static prefix must be compatible with
    a pattern (either side extending the other) or with an exact entry
    it prefixes."""
    if "*" not in glob:
        if glob in metrics_reg:
            return True
        return any(entry.endswith("*") and glob.startswith(entry[:-1])
                   for entry in metrics_reg)
    prefix = glob.split("*", 1)[0]
    for entry in metrics_reg:
        if entry.endswith("*"):
            ep = entry[:-1]
            if prefix.startswith(ep) or ep.startswith(prefix):
                return True
        elif entry.startswith(prefix):
            return True
    return False


def _string_literals(files: Sequence[SourceFile]) -> Set[str]:
    out: Set[str] = set()
    for sf in files:
        if _self_excluded(sf.relpath):
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                out.add(node.value)
    return out


def _registry_lines(repo_root: str) -> Dict[str, int]:
    """{entry string: line in registry.py} so registry-side findings
    point at the entry to delete/fix."""
    path = os.path.join(repo_root, "reporter_tpu", "analysis",
                        "registry.py")
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


REGISTRY_REL = "reporter_tpu/analysis/registry.py"
README_REL = "README.md"


def run(files: Sequence[SourceFile], repo_root: str,
        knobs: Optional[Dict[str, str]] = None,
        metrics_reg: Optional[Dict[str, str]] = None,
        readme_text: Optional[str] = None,
        full_scope: bool = True) -> List[Finding]:
    """``full_scope=False`` (a partial / fixture run) checks only the
    code -> registry direction: the reverse directions (dead knobs, dead
    metrics, README drift) need the whole package in view."""
    knobs = dict(registry.ENV_KNOBS if knobs is None else knobs)
    metrics_reg = dict(registry.METRICS if metrics_reg is None
                       else metrics_reg)
    if readme_text is None:
        try:
            with open(os.path.join(repo_root, "README.md"),
                      encoding="utf-8") as f:
                readme_text = f.read()
        except OSError:
            readme_text = ""
    reg_lines = _registry_lines(repo_root)
    findings: List[Finding] = []

    # ---- KN001: code <-> registry ------------------------------------------
    mentions = _knob_mentions(files)
    for name in sorted(mentions):
        if name not in knobs:
            rel, line = mentions[name]
            findings.append(Finding(
                rel, line, "KN001",
                f"{name} is read/set here but not in "
                f"registry.ENV_KNOBS — register it (and add a README "
                "knob-table row)"))
    if full_scope:
        cpp = _cpp_knob_mentions(repo_root)
        for name in sorted(knobs):
            if name not in mentions and name not in cpp:
                findings.append(Finding(
                    REGISTRY_REL, reg_lines.get(name, 1), "KN001",
                    f"registered knob {name} is mentioned nowhere in "
                    "the code — dead entry, remove it"))

    # ---- KN002: registry <-> README table ----------------------------------
    if full_scope:
        table = parse_readme_knobs(readme_text)
        for name in sorted(knobs):
            if name not in table:
                findings.append(Finding(
                    REGISTRY_REL, reg_lines.get(name, 1), "KN002",
                    f"registered knob {name} has no row in README's "
                    "knob table — document it (full variable name)"))
        for name in sorted(table):
            if name not in knobs:
                findings.append(Finding(
                    README_REL, table[name], "KN002",
                    f"README documents {name} but it is not in "
                    "registry.ENV_KNOBS — stale doc or missing "
                    "registration"))

    # ---- MT001: call sites -> registry -------------------------------------
    sites = _metric_sites(files)
    for rel, line, glob in sites:
        if not _covered(glob, metrics_reg):
            findings.append(Finding(
                rel, line, "MT001",
                f"metric name {glob!r} is not covered by "
                "registry.METRICS — register it (exact, or a "
                "'prefix.*' pattern for dynamic families)"))

    # ---- MT002: registry -> code literals ----------------------------------
    if full_scope:
        # a derived name (``<name>.cpu``, ``.cpu_wall``) has its site,
        # not a literal
        literals = _string_literals(files) | {
            glob for _rel, _line, glob in sites if "*" not in glob}
        for entry in sorted(metrics_reg):
            if entry.endswith("*"):
                continue  # dynamic family: call sites are f-strings
            if entry not in literals:
                findings.append(Finding(
                    REGISTRY_REL, reg_lines.get(entry, 1), "MT002",
                    f"registry metric {entry!r} matches no string "
                    "literal in the code — dead entry, remove it"))

    return findings
