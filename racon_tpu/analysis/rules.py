"""The contract rules.

Each rule cross-checks one hand-maintained invariant against its
machine-readable registry (see docs/ANALYSIS.md for the catalog):

  env-contract   ENV001-ENV005  code <-> utils/envspec.py <-> docs
  fault-site     FLT001-FLT002  hook literals <-> faults.SITES <-> tests
  metrics        MET001-MET004  recorded keys <-> metrics.METRIC_SPECS
                                <-> merge_kind <-> docs/OBSERVABILITY.md
  span-schema    SPAN001-SPAN003 Tracer emissions <-> obs_report tables
  atomic-write   ATM001         no bare open(w) in durable-output dirs
  lock-discipline LCK001        # guarded-by: attrs mutate under lock
  choke-point    CHK001         device_put inside retry.call closures
  determinism    DET001         no wallclock/PRNG in identity paths
  histogram      HIS001         record_hist <-> HIST_BUCKETS <->
                                METRIC_SPECS 'hist' rows <-> exporter

Registry-direction checks (dead declarations, doc drift, coverage)
only run in full-repo mode (``ctx.full``); per-file directions also
fire on single fixture files.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from typing import Dict, Iterator, List, Optional, Tuple

from racon_tpu.analysis.engine import Context, Finding, Rule

_ENV_PREFIX = "RACON_TPU_"


# ----------------------------------------------------------- ast helpers

def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _str_key(node: ast.AST) -> Optional[str]:
    """Static text of a string expression; dynamic f-string pieces
    become ``*``. IfExp is resolved per branch by the callers."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        out = []
        for part in node.values:
            if isinstance(part, ast.Constant):
                out.append(str(part.value))
            else:
                out.append("*")
        return "".join(out)
    return None


def _str_keys(node: ast.AST) -> List[str]:
    """Like _str_key but flattens conditional expressions (the
    ``f"res_ckpt_{e}s" if ... else "res_ckpt_resumes"`` idiom)."""
    if isinstance(node, ast.IfExp):
        return _str_keys(node.body) + _str_keys(node.orelse)
    k = _str_key(node)
    return [k] if k is not None else []


def _resolve_name(node: ast.AST, consts: Dict[str, str]) -> \
        Optional[str]:
    """Resolve an env-name argument: string literal, module constant
    (``ENV_FAULTS``), or attribute constant (``fleet.ENV_OBS_DIR``).
    None when not statically resolvable (function parameters)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.Attribute):
        return consts.get(node.attr)
    return None


def _func_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


# ========================================================== env-contract

def _iter_env_reads(tree: ast.Module) -> Iterator[Tuple[int, ast.AST]]:
    """(lineno, name-expression) for every os.environ read:
    ``environ.get(X, ...)``, ``os.getenv(X, ...)``, ``environ[X]``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "get" and \
                    _unparse(f.value).endswith("environ") and node.args:
                yield node.lineno, node.args[0]
            elif _func_name(node) == "getenv" and node.args:
                yield node.lineno, node.args[0]
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load) and \
                _unparse(node.value).endswith("environ"):
            yield node.lineno, node.slice


def check_env_contract(ctx: Context) -> Iterator[Finding]:
    consts = ctx.module_consts()
    registry = ctx.env_registry()

    # ENV001/ENV002: every read resolves through a declared spec.
    for path in ctx.scoped("racon_tpu/", "scripts/"):
        rel = ctx.rel(path)
        if rel == "racon_tpu/utils/envspec.py":
            continue
        tree = ctx.tree(path)
        if tree is None:
            continue
        for lineno, arg in _iter_env_reads(tree):
            name = _resolve_name(arg, consts)
            if name is None or not name.startswith(_ENV_PREFIX):
                continue
            if ctx.pragma(path, lineno, "env-ok"):
                continue
            yield Finding(
                "ENV001", "error", rel, lineno,
                f"raw environment read of {name}: route it through "
                f"racon_tpu.utils.envspec.read")
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    _func_name(node) == "read" and \
                    isinstance(node.func, ast.Attribute) and \
                    _unparse(node.func.value).endswith("envspec") and \
                    node.args:
                name = _resolve_name(node.args[0], consts)
                if name is not None and name not in registry:
                    yield Finding(
                        "ENV002", "error", rel, node.lineno,
                        f"envspec.read of undeclared gate {name}: "
                        f"declare it in racon_tpu/utils/envspec.py")

    if not ctx.full:
        return

    # Declaration line numbers for registry-direction findings.
    spec_rel = "racon_tpu/utils/envspec.py"
    spec_path = None
    for f in ctx.files:
        if ctx.rel(f) == spec_rel:
            spec_path = f
    spec_lines = ctx.lines(spec_path) if spec_path else []

    def decl_line(name: str) -> int:
        for i, ln in enumerate(spec_lines, 1):
            if f'"{name}"' in ln:
                return i
        return 1

    # Name -> is it read anywhere (textual: the code keeps ENV_*
    # constants, so the full name appears at its declaration site).
    corpus = {ctx.rel(f): ctx.source(f)
              for f in ctx.scoped("racon_tpu/", "scripts/")
              if ctx.rel(f) != spec_rel}
    blob = "\n".join(corpus.values())
    docs = ctx.doc_files()

    for name, spec in sorted(registry.items()):
        # ENV003: declared but never read.
        if name not in blob:
            yield Finding(
                "ENV003", "error", spec_rel, decl_line(name),
                f"declared gate {name} is read nowhere in racon_tpu/ "
                f"or scripts/: delete the declaration")
        # ENV004: declared but missing from its doc file.
        doc = getattr(spec, "doc", None) or (
            spec.get("doc") if isinstance(spec, dict) else None)
        if doc is not None and name not in docs.get(doc, ""):
            yield Finding(
                "ENV004", "error", spec_rel, decl_line(name),
                f"declared gate {name} has no row in docs/{doc}")

    # ENV005: documented names that no declaration covers. A token
    # ending in ``_`` is a family mention (RACON_TPU_AUTOSCALE_*) and
    # matches by prefix.
    tok_re = re.compile(r"RACON_TPU_[A-Z0-9_]*")
    for doc_name, text in sorted(docs.items()):
        for i, ln in enumerate(text.splitlines(), 1):
            for tok in tok_re.findall(ln):
                if tok in registry:
                    continue
                if tok.endswith("_") and any(
                        n.startswith(tok) for n in registry):
                    continue
                yield Finding(
                    "ENV005", "error",
                    ("README.md" if doc_name == "README.md"
                     else f"docs/{doc_name}"), i,
                    f"documented gate {tok} is not declared in "
                    f"racon_tpu/utils/envspec.py")


# ============================================================ fault-site

def _iter_fault_sites(tree: ast.Module) -> \
        Iterator[Tuple[int, str, bool]]:
    """(lineno, site-pattern, is_prefix) for literals handed to
    maybe_fault/maybe_torn and retry ``call`` sites."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fname = _func_name(node)
        hook = fname in ("maybe_fault", "maybe_torn")
        retry = fname in ("retry_call",) or (
            fname == "call" and isinstance(node.func, ast.Attribute)
            and _unparse(node.func.value).split(".")[-1] in
            ("retry", "_retry"))
        if not (hook or retry):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and \
                isinstance(arg.value, str):
            if retry and "/" not in arg.value:
                continue  # retry.call with a non-site label
            yield node.lineno, arg.value, False
        elif isinstance(arg, ast.JoinedStr):
            key = _str_key(arg) or ""
            prefix = key.split("*", 1)[0]
            yield node.lineno, prefix, True


def check_fault_site(ctx: Context) -> Iterator[Finding]:
    sites = set(ctx.fault_sites())
    prefixes = tuple(ctx.fault_prefixes())

    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        if rel == "racon_tpu/resilience/faults.py":
            continue
        tree = ctx.tree(path)
        if tree is None:
            continue
        for lineno, site, is_prefix in _iter_fault_sites(tree):
            if ctx.pragma(path, lineno, "fault-site-ok"):
                continue
            if is_prefix:
                ok = any(site.startswith(p) for p in prefixes)
            else:
                ok = site in sites or \
                    any(site.startswith(p) for p in prefixes)
            if not ok:
                yield Finding(
                    "FLT001", "error", rel, lineno,
                    f"fault site {site!r} is not declared in "
                    f"racon_tpu/resilience/faults.py SITES")

    if not ctx.full:
        return

    # FLT002: every declared site exercised by a test or smoke script.
    import os
    corpus = []
    for top in ("tests", "scripts"):
        base = os.path.join(ctx.root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__",)]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    try:
                        with open(os.path.join(dirpath, fn), "r",
                                  encoding="utf-8") as fh:
                            corpus.append(fh.read())
                    except OSError:
                        pass
    blob = "\n".join(corpus)
    faults_rel = "racon_tpu/resilience/faults.py"
    faults_src = ""
    for f in ctx.files:
        if ctx.rel(f) == faults_rel:
            faults_src = ctx.source(f)

    def site_line(site: str) -> int:
        for i, ln in enumerate(faults_src.splitlines(), 1):
            if f'"{site}"' in ln:
                return i
        return 1

    for site in sorted(set(ctx.fault_sites()) | set(prefixes)):
        if site not in blob:
            yield Finding(
                "FLT002", "error", faults_rel, site_line(site),
                f"declared fault site {site!r} is exercised by no "
                f"test or smoke script")


# ======================================================= metrics-contract

_KEY_RE = re.compile(r"^[a-z_][a-z0-9_*]*$")


def _iter_metric_keys(ctx: Context, path: str) -> \
        Iterator[Tuple[int, str]]:
    tree = ctx.tree(path)
    if tree is None:
        return
    in_metrics = ctx.rel(path) == "racon_tpu/obs/metrics.py"
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("inc", "set", "max") and node.args:
            for key in _str_keys(node.args[0]):
                if _KEY_RE.match(key):
                    yield node.lineno, key
        # timed_span(kind, name, ...) records <kind>_seconds_<name>.
        elif isinstance(node, ast.Call) and \
                _func_name(node) == "timed_span" and len(node.args) >= 2:
            kind, name = (_str_key(a) for a in node.args[:2])
            if kind is not None and name is not None:
                key = f"{kind}_seconds_{name}"
                if _KEY_RE.match(key):
                    yield node.lineno, key
        # The reg.apply(mutator) convention in obs/metrics.py: the
        # mutator's dict parameter is named ``v`` and its subscript
        # stores are recorded keys (docs/ANALYSIS.md).
        elif in_metrics and isinstance(node,
                                       (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Subscript) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "v":
                    for key in _str_keys(tgt.slice):
                        if _KEY_RE.match(key):
                            yield node.lineno, key


def _key_matches(key: str, pattern: str) -> bool:
    """``pipe_stage_*_items`` covers ``pipe_stage_encode_items`` and
    the statically-extracted ``pipe_stage_*_items`` itself (dynamic
    f-string segments become ``*`` on both sides; concretize the key's
    stars before matching)."""
    return fnmatch.fnmatchcase(key.replace("*", "x"), pattern)


def check_metrics_contract(ctx: Context) -> Iterator[Finding]:
    specs = ctx.metric_specs()
    patterns = [s[0] for s in specs]

    recorded = set()
    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        for lineno, key in _iter_metric_keys(ctx, path):
            recorded.add(key)
            if key.startswith("_"):
                continue  # internal, excluded from snapshots
            if ctx.pragma(path, lineno, "metric-ok"):
                continue
            if not any(_key_matches(key, p) for p in patterns):
                yield Finding(
                    "MET001", "error", rel, lineno,
                    f"metric key {key!r} matches no METRIC_SPECS row "
                    f"in racon_tpu/obs/metrics.py")

    if not ctx.full:
        return

    metrics_rel = "racon_tpu/obs/metrics.py"
    metrics_src = ""
    corpus_blob = []
    for f in ctx.scoped("racon_tpu/"):
        if ctx.rel(f) == metrics_rel:
            metrics_src = ctx.source(f)
        corpus_blob.append(ctx.source(f))
    blob = "\n".join(corpus_blob)
    obs_doc = ctx.doc_text("OBSERVABILITY.md")

    def spec_line(pattern: str) -> int:
        for i, ln in enumerate(metrics_src.splitlines(), 1):
            if f'("{pattern}"' in ln:
                return i
        return 1

    from racon_tpu.obs import metrics as metrics_mod
    for pattern, kind, doc_token in specs:
        token = pattern.split("*", 1)[0]
        # MET002: spec with no producer anywhere in racon_tpu/ (a key
        # that only a timed_span call spells out counts as produced).
        if token and token not in blob and \
                not any(_key_matches(k, pattern) for k in recorded):
            yield Finding(
                "MET002", "error", metrics_rel, spec_line(pattern),
                f"METRIC_SPECS row {pattern!r} has no producer in "
                f"racon_tpu/ (dead spec)")
        # MET003: spec with no docs row.
        if doc_token not in obs_doc:
            yield Finding(
                "MET003", "error", metrics_rel, spec_line(pattern),
                f"METRIC_SPECS row {pattern!r}: doc token "
                f"{doc_token!r} not found in docs/OBSERVABILITY.md")
        # MET004: declared merge kind must agree with merge_kind(),
        # i.e. with what fleet.aggregate will actually do.
        concrete = pattern.replace("*", "x")
        actual = metrics_mod.merge_kind(concrete)
        if actual != kind:
            yield Finding(
                "MET004", "error", metrics_rel, spec_line(pattern),
                f"METRIC_SPECS row {pattern!r} declares merge kind "
                f"{kind!r} but merge_kind({concrete!r}) = {actual!r}")


# =========================================================== span-schema

_TRACERY = re.compile(r"(^|\.)get_tracer\(\)$")


def _iter_span_emits(tree: ast.Module) -> \
        Iterator[Tuple[int, str, Optional[set]]]:
    """(lineno, kind, kwarg-names or None-when-splatted) for every
    Tracer .span/.point/.emit call and every obs/metrics.py
    ``timed_span`` call (which opens a span of its first argument's
    kind). Receiver heuristic: a bare ``tracer``/``tr`` name or a
    ``get_tracer()`` call — io indexes and other .span APIs don't
    match."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if _func_name(node) != "timed_span":
            if not isinstance(f, ast.Attribute) or \
                    f.attr not in ("span", "point", "emit"):
                continue
            recv = _unparse(f.value)
            if not (recv in ("tracer", "tr") or _TRACERY.search(recv)):
                continue
        if not node.args:
            continue
        kind = _str_key(node.args[0])
        if kind is None or "*" in kind:
            continue
        if any(kw.arg is None for kw in node.keywords):
            kwargs: Optional[set] = None       # **splat: not static
        else:
            kwargs = {kw.arg for kw in node.keywords}
        yield node.lineno, kind, kwargs


def check_span_schema(ctx: Context) -> Iterator[Finding]:
    required = ctx.span_required()
    free = set(ctx.span_attr_free())
    legal = set(required) | free
    emitted: Dict[str, str] = {}

    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        tree = ctx.tree(path)
        if tree is None:
            continue
        for lineno, kind, kwargs in _iter_span_emits(tree):
            emitted.setdefault(kind, f"{rel}:{lineno}")
            if ctx.pragma(path, lineno, "span-ok"):
                continue
            if kind not in legal:
                yield Finding(
                    "SPAN001", "error", rel, lineno,
                    f"span kind {kind!r} is not in "
                    f"scripts/obs_report.py KIND_REQUIRED_ATTRS or "
                    f"ATTR_FREE_KINDS")
                continue
            need = required.get(kind, ())
            if need and kwargs is not None:
                missing = [a for a in need if a not in kwargs]
                if missing:
                    yield Finding(
                        "SPAN002", "error", rel, lineno,
                        f"span kind {kind!r} emitted without required "
                        f"attrs {missing} (obs_report.py validator "
                        f"will reject the trace)")

    if not ctx.full:
        return

    # SPAN003: validator kinds nobody emits (dead schema).
    report_rel = "scripts/obs_report.py"
    for kind in sorted(legal):
        if kind not in emitted:
            yield Finding(
                "SPAN003", "error", report_rel, 1,
                f"span kind {kind!r} is validated in obs_report.py "
                f"but emitted nowhere")


# ========================================================== atomic-write

def check_atomic_write(ctx: Context) -> Iterator[Finding]:
    for path in ctx.scoped("racon_tpu/cache/", "racon_tpu/distributed/",
                           "racon_tpu/gateway/",
                           "racon_tpu/resilience/", "racon_tpu/obs/"):
        rel = ctx.rel(path)
        if rel == "racon_tpu/utils/atomicio.py":
            continue
        tree = ctx.tree(path)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Name) and
                    node.func.id == "open"):
                continue
            mode = None
            if len(node.args) >= 2:
                mode = _str_key(node.args[1])
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = _str_key(kw.value)
            if mode is None or not any(c in mode for c in "wx"):
                continue
            if ctx.pragma(path, node.lineno, "atomic-ok"):
                continue
            yield Finding(
                "ATM001", "error", rel, node.lineno,
                f"bare open(..., {mode!r}) under a durable-output "
                f"tree: use racon_tpu.utils.atomicio "
                f"(atomic_write_bytes / atomic_writer / "
                f"publish_exclusive)")


# ======================================================== lock-discipline

_GUARD_RE = re.compile(
    r"self\.(\w+)\b[^#]*#\s*guarded-by:\s*(\w+)")
_MUTATORS = ("append", "add", "extend", "insert", "remove", "pop",
             "popitem", "clear", "update", "setdefault", "discard")


class _LockWalk(ast.NodeVisitor):
    def __init__(self, guarded: Dict[str, str]):
        self.guarded = guarded
        self.held: List[str] = []
        self.hits: List[Tuple[int, str, str]] = []

    def visit_With(self, node: ast.With):
        names = []
        for item in node.items:
            src = _unparse(item.context_expr)
            for attr, lock in self.guarded.items():
                if src in (f"self.{lock}", f"self.{lock}:"):
                    names.append(lock)
        self.held.extend(names)
        self.generic_visit(node)
        for _ in names:
            self.held.pop()

    def _attr_of(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self" and node.attr in self.guarded:
            return node.attr
        return None

    def _flag(self, lineno: int, attr: str):
        lock = self.guarded[attr]
        if lock not in self.held:
            self.hits.append((lineno, attr, lock))

    def visit_Assign(self, node: ast.Assign):
        for tgt in node.targets:
            a = self._attr_of(tgt)
            if a:
                self._flag(node.lineno, a)
            if isinstance(tgt, ast.Subscript):
                a = self._attr_of(tgt.value)
                if a:
                    self._flag(node.lineno, a)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        a = self._attr_of(node.target)
        if a:
            self._flag(node.lineno, a)
        if isinstance(node.target, ast.Subscript):
            a = self._attr_of(node.target.value)
            if a:
                self._flag(node.lineno, a)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _MUTATORS:
            a = self._attr_of(f.value)
            if a:
                self._flag(node.lineno, a)
        self.generic_visit(node)


def check_lock_discipline(ctx: Context) -> Iterator[Finding]:
    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        tree = ctx.tree(path)
        if tree is None:
            continue
        src_lines = ctx.lines(path)
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            end = getattr(cls, "end_lineno", None) or len(src_lines)
            guarded: Dict[str, str] = {}
            for ln in src_lines[cls.lineno - 1:end]:
                m = _GUARD_RE.search(ln)
                if m:
                    guarded[m.group(1)] = m.group(2)
            if not guarded:
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name == "__init__":
                    continue  # construction precedes sharing
                if ctx.pragma(path, fn.lineno, "unlocked-ok"):
                    continue
                walker = _LockWalk(guarded)
                walker.visit(fn)
                for lineno, attr, lock in walker.hits:
                    if ctx.pragma(path, lineno, "unlocked-ok"):
                        continue
                    yield Finding(
                        "LCK001", "error", rel, lineno,
                        f"{cls.name}.{attr} is declared guarded-by "
                        f"{lock} but is mutated outside 'with "
                        f"self.{lock}'")


# =========================================================== choke-point

def check_choke_point(ctx: Context) -> Iterator[Finding]:
    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        tree = ctx.tree(path)
        if tree is None:
            continue
        # Function names handed to retry.call / watchdog guard in this
        # module: device_put inside those closures is envelope-covered.
        wrapped = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _func_name(node) in \
                    ("retry_call", "call", "guard"):
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        wrapped.add(arg.id)
        # Walk with the enclosing-function stack.
        stack: List[str] = []

        def walk(node):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                stack.append(node.name)
                for child in ast.iter_child_nodes(node):
                    yield from walk(child)
                stack.pop()
                return
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "device_put" and \
                    _unparse(node.func.value) == "jax":
                if not any(n in wrapped for n in stack) and \
                        not ctx.pragma(path, node.lineno,
                                       "unguarded-ok"):
                    yield Finding(
                        "CHK001", "error", rel, node.lineno,
                        "jax.device_put outside a resilience.retry"
                        ".call / watchdog-guarded closure: a wedged "
                        "transfer here hangs the worker with no "
                        "deadline")
            for child in ast.iter_child_nodes(node):
                yield from walk(child)

        yield from walk(tree)


# ============================================================= histogram

def _iter_record_hist(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """(lineno, family) for every ``record_hist(<literal>, ...)`` call
    whose family name is statically resolvable."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _func_name(node) == "record_hist" and node.args:
            name = _str_key(node.args[0])
            if name is not None and "*" not in name:
                yield node.lineno, name


def check_histogram(ctx: Context) -> Iterator[Finding]:
    buckets = ctx.hist_buckets()

    # HIS001 (per-file direction): every recorded family has declared
    # bucket bounds — record_hist raises at runtime otherwise, and the
    # linter catches the site before any test exercises it.
    for path in ctx.scoped("racon_tpu/", "scripts/"):
        rel = ctx.rel(path)
        tree = ctx.tree(path)
        if tree is None:
            continue
        for lineno, name in _iter_record_hist(tree):
            if ctx.pragma(path, lineno, "hist-ok"):
                continue
            if name not in buckets:
                yield Finding(
                    "HIS001", "error", rel, lineno,
                    f"record_hist family {name!r} has no bucket bounds "
                    f"declared in racon_tpu/obs/metrics.py "
                    f"HIST_BUCKETS")

    if not ctx.full:
        return

    metrics_rel = "racon_tpu/obs/metrics.py"
    metrics_src = ""
    export_src = ""
    corpus = []
    for f in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(f)
        if rel == metrics_rel:
            metrics_src = ctx.source(f)
        elif rel == "racon_tpu/obs/export.py":
            export_src = ctx.source(f)
        corpus.append(ctx.source(f))
    blob = "\n".join(corpus)

    def bucket_line(name: str) -> int:
        for i, ln in enumerate(metrics_src.splitlines(), 1):
            if f'"{name}"' in ln:
                return i
        return 1

    # Registry directions: buckets <-> METRIC_SPECS 'hist' rows agree
    # both ways, and every declared family has a producer somewhere.
    hist_specs = {s[0] for s in ctx.metric_specs() if s[1] == "hist"}
    for name in sorted(buckets):
        if name not in hist_specs:
            yield Finding(
                "HIS001", "error", metrics_rel, bucket_line(name),
                f"HIST_BUCKETS family {name!r} has no METRIC_SPECS "
                f"row with merge kind 'hist' (fleet aggregation would "
                f"not fold its buckets)")
        if f'record_hist("{name}"' not in blob:
            yield Finding(
                "HIS001", "error", metrics_rel, bucket_line(name),
                f"HIST_BUCKETS family {name!r} is recorded nowhere "
                f"(no record_hist call in racon_tpu/)")
    for pattern in sorted(hist_specs):
        if pattern not in buckets:
            yield Finding(
                "HIS001", "error", metrics_rel, bucket_line(pattern),
                f"METRIC_SPECS row {pattern!r} declares merge kind "
                f"'hist' but HIST_BUCKETS has no bounds for it")
    if buckets and ('le="' not in export_src or
                    "_bucket" not in export_src):
        yield Finding(
            "HIS001", "error", "racon_tpu/obs/export.py", 1,
            "histogram families are declared but obs/export.py has no "
            "OpenMetrics histogram rendering (_bucket samples with le "
            "labels)")


# =========================================================== determinism

_WALLCLOCK = ("time.time", "time.time_ns", "datetime.now",
              "datetime.datetime.now", "datetime.utcnow",
              "datetime.datetime.utcnow")
_DET_FILES = ("racon_tpu/distributed/ledger.py",
              "racon_tpu/resilience/checkpoint.py")
_DET_FN = re.compile(r"fingerprint|nonce")
_BLESSED_FN = ("_now",)


def check_determinism(ctx: Context) -> Iterator[Finding]:
    for path in ctx.scoped("racon_tpu/"):
        rel = ctx.rel(path)
        whole_file = rel in _DET_FILES or not ctx.full
        tree = ctx.tree(path)
        if tree is None:
            continue
        stack: List[str] = []

        def in_scope() -> bool:
            if any(fn in _BLESSED_FN for fn in stack):
                return False
            if whole_file and stack:
                return True
            return any(_DET_FN.search(fn) for fn in stack)

        def offender(node: ast.Call) -> Optional[str]:
            src = _unparse(node.func)
            if src in _WALLCLOCK:
                return src
            head = src.split(".", 1)[0]
            if head in ("random", "uuid"):
                return src
            return None

        def walk(node):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                stack.append(node.name)
                for child in ast.iter_child_nodes(node):
                    yield from walk(child)
                stack.pop()
                return
            if isinstance(node, ast.Call) and in_scope():
                off = offender(node)
                if off and not ctx.pragma(path, node.lineno,
                                          "wallclock-ok"):
                    yield Finding(
                        "DET001", "error", rel, node.lineno,
                        f"{off} in a fingerprint/ledger/checkpoint "
                        f"path: identity and lease state must be "
                        f"deterministic (use the _now shim or "
                        f"os.urandom)")
            for child in ast.iter_child_nodes(node):
                yield from walk(child)

        yield from walk(tree)


# ================================================================ the set

ALL_RULES = (
    Rule("env-contract",
         ("ENV001", "ENV002", "ENV003", "ENV004", "ENV005"), "error",
         "every RACON_TPU_* read routes through utils/envspec.py and "
         "code, registry, and docs agree in both directions",
         check_env_contract),
    Rule("fault-site", ("FLT001", "FLT002"), "error",
         "fault-hook literals match faults.SITES and every declared "
         "site is exercised by a test or smoke script",
         check_fault_site),
    Rule("metrics-contract",
         ("MET001", "MET002", "MET003", "MET004"), "error",
         "recorded registry keys match METRIC_SPECS; specs have a "
         "producer, a docs row, and the correct fleet merge kind",
         check_metrics_contract),
    Rule("span-schema", ("SPAN001", "SPAN002", "SPAN003"), "error",
         "Tracer emissions and the obs_report.py validators agree on "
         "span kinds and required attrs in both directions",
         check_span_schema),
    Rule("atomic-write", ("ATM001",), "error",
         "no bare open(w) under ledger/checkpoint/obs trees outside "
         "utils/atomicio.py", check_atomic_write),
    Rule("lock-discipline", ("LCK001",), "error",
         "# guarded-by: attrs are only mutated under their lock",
         check_lock_discipline),
    Rule("choke-point", ("CHK001",), "error",
         "jax.device_put sites sit inside retry/watchdog-guarded "
         "closures", check_choke_point),
    Rule("determinism", ("DET001",), "error",
         "no wallclock/PRNG in fingerprint, ledger, or checkpoint "
         "paths outside the blessed shims", check_determinism),
    Rule("histogram", ("HIS001",), "error",
         "record_hist families, HIST_BUCKETS bounds, METRIC_SPECS "
         "'hist' rows, and the OpenMetrics exporter agree in every "
         "direction", check_histogram),
)
