"""Rule engine for the contract linter.

Plain AST walking over the repo's own sources: file discovery, cached
parse trees, a pragma convention for blessed exceptions, a finding
model with file:line + rule id + severity, baseline suppression for
grandfathered findings, and byte-stable text/JSON reports (sorted,
fixed separators — two runs on the same tree produce identical bytes,
so the CI gate can diff them).

Pragmas: a rule-named tag in a ``# lint: <tag> (...)`` comment on the
flagged line (or the line directly above it) suppresses that rule at
that site — e.g. ``# lint: atomic-ok (torn-write drill)``. The tag
spelling each rule honours is part of the rule catalog in
docs/ANALYSIS.md.
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional


class Finding(NamedTuple):
    rule: str      # rule id, e.g. "ENV001"
    severity: str  # "error" | "warn"
    path: str      # repo-relative posix path
    line: int      # 1-indexed
    message: str

    @property
    def fingerprint(self) -> str:
        """Baseline identity: stable across line drift (a finding that
        merely moves does not escape its suppression, and a new finding
        with the same shape elsewhere in the file is still new only if
        its message differs)."""
        return f"{self.rule}:{self.path}:{self.message}"


class Rule(NamedTuple):
    name: str            # e.g. "env-contract"
    ids: tuple           # finding ids this rule can emit
    severity: str
    summary: str         # one-liner for the catalog / reports
    check: Callable      # check(ctx) -> Iterable[Finding]


_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache"}


def discover_files(root: str) -> List[str]:
    """Default lint corpus: every .py under racon_tpu/ and scripts/.
    tests/ are deliberately out — fixtures under
    tests/fixtures/analysis/ carry seeded violations."""
    out: List[str] = []
    for top in ("racon_tpu", "scripts"):
        base = os.path.join(root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_DIRS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


class Context:
    """Shared state for one lint run.

    ``full`` distinguishes the repo-wide run (registry<->code<->docs
    direction checks enabled, rule path scopes applied) from a fixture
    run (``full=False``: only the per-file directions fire, and every
    supplied file is in scope regardless of its path — that is how the
    seeded-violation fixtures under tests/fixtures/analysis/ exercise
    rules whose repo scope they live outside of).

    The ``*_override`` kwargs let tests inject synthetic registries to
    exercise the registry-direction findings (dead declaration,
    undocumented gate, ...) without mutating the real tables.
    """

    def __init__(self, root: str, files: Optional[List[str]] = None,
                 full: bool = True, *,
                 env_registry: Optional[Dict] = None,
                 metric_specs: Optional[tuple] = None,
                 fault_sites: Optional[tuple] = None,
                 fault_prefixes: Optional[tuple] = None,
                 span_required: Optional[Dict] = None,
                 span_attr_free: Optional[tuple] = None,
                 hist_buckets: Optional[Dict] = None,
                 docs_override: Optional[Dict[str, str]] = None):
        self.root = os.path.abspath(root)
        self.files = files if files is not None else \
            discover_files(self.root)
        self.full = full
        self._src: Dict[str, str] = {}
        self._tree: Dict[str, Optional[ast.Module]] = {}
        self._consts: Optional[Dict[str, str]] = None
        self._env_registry = env_registry
        self._metric_specs = metric_specs
        self._fault_sites = fault_sites
        self._fault_prefixes = fault_prefixes
        self._span_required = span_required
        self._span_attr_free = span_attr_free
        self._hist_buckets = hist_buckets
        self._docs_override = docs_override

    # ------------------------------------------------------- file access

    def rel(self, path: str) -> str:
        p = os.path.abspath(path)
        if p.startswith(self.root + os.sep):
            p = p[len(self.root) + 1:]
        return p.replace(os.sep, "/")

    def source(self, path: str) -> str:
        if path not in self._src:
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    self._src[path] = fh.read()
            except OSError:
                self._src[path] = ""
        return self._src[path]

    def lines(self, path: str) -> List[str]:
        return self.source(path).splitlines()

    def tree(self, path: str) -> Optional[ast.Module]:
        if path not in self._tree:
            try:
                self._tree[path] = ast.parse(self.source(path))
            except SyntaxError:
                self._tree[path] = None
        return self._tree[path]

    def scoped(self, *prefixes: str) -> List[str]:
        """Files under any of the repo-relative prefixes. In fixture
        mode every supplied file is in scope."""
        if not self.full:
            return list(self.files)
        out = []
        for f in self.files:
            r = self.rel(f)
            if any(r == p or r.startswith(p) for p in prefixes):
                out.append(f)
        return out

    def pragma(self, path: str, lineno: int, tag: str) -> bool:
        """True when ``# lint: <tag>`` annotates ``lineno`` or the line
        directly above it."""
        lines = self.lines(path)
        pat = re.compile(r"#\s*lint:\s*" + re.escape(tag) + r"\b")
        for ln in (lineno, lineno - 1):
            if 1 <= ln <= len(lines) and pat.search(lines[ln - 1]):
                return True
        return False

    # ----------------------------------------------------- shared lookups

    def module_consts(self) -> Dict[str, str]:
        """Repo-wide map of top-level UPPER_CASE string constants
        (``ENV_FAULTS = "RACON_TPU_FAULTS"``) by bare name, used to
        resolve Name/Attribute arguments of env reads and
        ``envspec.read`` calls."""
        if self._consts is None:
            consts: Dict[str, str] = {}
            for f in self.files:
                t = self.tree(f)
                if t is None:
                    continue
                for node in t.body:
                    if isinstance(node, ast.Assign) and \
                       isinstance(node.value, ast.Constant) and \
                       isinstance(node.value.value, str):
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name) and \
                               tgt.id.isupper():
                                consts[tgt.id] = node.value.value
            self._consts = consts
        return self._consts

    def doc_text(self, name: str) -> str:
        if self._docs_override is not None:
            return self._docs_override.get(name, "")
        try:
            with open(os.path.join(self.root, "docs", name), "r",
                      encoding="utf-8") as fh:
                return fh.read()
        except OSError:
            return ""

    def doc_files(self) -> Dict[str, str]:
        """name -> text for every docs/*.md (plus README.md)."""
        if self._docs_override is not None:
            return dict(self._docs_override)
        out: Dict[str, str] = {}
        docs = os.path.join(self.root, "docs")
        if os.path.isdir(docs):
            for fn in sorted(os.listdir(docs)):
                if fn.endswith(".md"):
                    out[fn] = self.doc_text(fn)
        readme = os.path.join(self.root, "README.md")
        if os.path.exists(readme):
            with open(readme, "r", encoding="utf-8") as fh:
                out["README.md"] = fh.read()
        return out

    # Registry loaders: the real tables unless a test injected fakes.

    def env_registry(self) -> Dict:
        if self._env_registry is not None:
            return self._env_registry
        from racon_tpu.utils import envspec
        return envspec.REGISTRY

    def metric_specs(self) -> tuple:
        if self._metric_specs is not None:
            return self._metric_specs
        from racon_tpu.obs import metrics
        return metrics.METRIC_SPECS

    def fault_sites(self) -> tuple:
        if self._fault_sites is not None:
            return self._fault_sites
        from racon_tpu.resilience import faults
        return faults.SITES

    def fault_prefixes(self) -> tuple:
        if self._fault_prefixes is not None:
            return self._fault_prefixes
        from racon_tpu.resilience import faults
        return faults.SITE_PREFIXES

    def hist_buckets(self) -> Dict:
        if self._hist_buckets is not None:
            return self._hist_buckets
        from racon_tpu.obs import metrics
        return metrics.HIST_BUCKETS

    def _span_tables(self):
        """(KIND_REQUIRED_ATTRS, ATTR_FREE_KINDS) parsed statically out
        of scripts/obs_report.py — the validator is a script, not a
        package, and the linter must not execute it."""
        path = os.path.join(self.root, "scripts", "obs_report.py")
        required: Dict[str, tuple] = {}
        free: tuple = ()
        try:
            tree = ast.parse(open(path, "r", encoding="utf-8").read())
        except (OSError, SyntaxError):
            return required, free
        for node in tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                if tgt.id == "KIND_REQUIRED_ATTRS":
                    try:
                        required = {k: tuple(v) for k, v in
                                    ast.literal_eval(node.value).items()}
                    except ValueError:
                        pass
                elif tgt.id == "ATTR_FREE_KINDS":
                    try:
                        free = tuple(ast.literal_eval(node.value))
                    except ValueError:
                        pass
        return required, free

    def span_required(self) -> Dict[str, tuple]:
        if self._span_required is not None:
            return self._span_required
        return self._span_tables()[0]

    def span_attr_free(self) -> tuple:
        if self._span_attr_free is not None:
            return self._span_attr_free
        return self._span_tables()[1]


def run_rules(rules: Iterable[Rule], ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.check(ctx))
    # Byte-stable order; dedup (two rules re-walking one tree may
    # reproduce an identical finding).
    return sorted(set(findings),
                  key=lambda f: (f.path, f.line, f.rule, f.message))


# -------------------------------------------------------------- baseline

def load_baseline(path: str) -> List[str]:
    """Grandfathered finding fingerprints (JSON list). Missing file =
    empty baseline: the repo lints clean or CI fails."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except OSError:
        return []
    if not isinstance(data, list):
        raise ValueError(f"[racon_tpu::analysis] baseline {path!r} "
                         "must be a JSON list of fingerprints")
    return [str(x) for x in data]


def split_findings(findings: List[Finding], baseline: List[str]):
    """(active, suppressed) partition by baseline fingerprint."""
    allowed = set(baseline)
    active = [f for f in findings if f.fingerprint not in allowed]
    suppressed = [f for f in findings if f.fingerprint in allowed]
    return active, suppressed


# --------------------------------------------------------------- reports

def render_text(findings: List[Finding],
                suppressed: Optional[List[Finding]] = None) -> str:
    out = []
    for f in findings:
        out.append(f"{f.path}:{f.line}: {f.rule} [{f.severity}] "
                   f"{f.message}")
    for f in suppressed or []:
        out.append(f"{f.path}:{f.line}: {f.rule} [baselined] "
                   f"{f.message}")
    return "\n".join(out) + ("\n" if out else "")


def render_json(findings: List[Finding],
                suppressed: Optional[List[Finding]] = None) -> str:
    def row(f: Finding, base: bool):
        return {"rule": f.rule, "severity": f.severity, "path": f.path,
                "line": f.line, "message": f.message,
                "baselined": base}
    rows = [row(f, False) for f in findings] + \
           [row(f, True) for f in suppressed or []]
    return json.dumps(rows, indent=2, sort_keys=True) + "\n"


def summary_line(findings: List[Finding], suppressed: List[Finding],
                 n_rules: int, n_files: int) -> str:
    """The burn-down line ci.sh logs grep for."""
    total = len(findings) + len(suppressed)
    return (f"lint_findings_total={total} active={len(findings)} "
            f"baselined={len(suppressed)} rules={n_rules} "
            f"files={n_files}")
