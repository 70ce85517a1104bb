"""Contract-linter tests (racon_tpu/analysis/, docs/ANALYSIS.md).

Two proofs per rule, both required by the meta-test at the bottom:

- ``test_<rule>_clean``: the rule finds nothing on the real repo — the
  contracts actually hold, so ci.sh can gate on an empty baseline;
- ``test_<rule>_fires``: the rule catches its seeded violation in
  tests/fixtures/analysis/ (per-file directions) or against a
  synthetic registry (registry-direction checks) — the rule is not
  vacuously green.

Plus engine-level behavior: pragma suppression, baseline partition,
byte-stable reports, and the scripts/lint.py --ci exit code.
"""

import json
import os
import subprocess
import sys

import pytest

from racon_tpu.analysis import (ALL_RULES, Context, Finding,
                                load_baseline, render_json, render_text,
                                run_rules, split_findings, summary_line)
from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.utils.envspec import EnvSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def _rule(name):
    return next(r for r in ALL_RULES if r.name == name)


def _fixture_ctx(*names, **overrides):
    files = [os.path.join(FIXTURES, n) for n in names]
    for f in files:
        assert os.path.exists(f), f
    return Context(REPO, files=files, full=False, **overrides)


def _ids(findings):
    return {f.rule for f in findings}


@pytest.fixture(scope="module")
def repo_findings():
    """One full-repo run shared by every clean-pass test."""
    return run_rules(ALL_RULES, Context(REPO))


def _clean(repo_findings, rule_name):
    rule = _rule(rule_name)
    hits = [f for f in repo_findings if f.rule in rule.ids]
    assert hits == [], "\n" + render_text(hits)


# ------------------------------------------------------------ env-contract


def test_env_contract_clean(repo_findings):
    _clean(repo_findings, "env-contract")


def test_env_contract_fires():
    found = list(_rule("env-contract").check(
        _fixture_ctx("env_violation.py")))
    assert {"ENV001", "ENV002"} <= _ids(found)

    # Registry directions: dead declaration + missing docs row ...
    ghost = EnvSpec("RACON_TPU_ZZ_GHOST", "", "flag", "ZZ.md", "ghost")
    ctx = Context(REPO, files=[], full=True,
                  env_registry={ghost.name: ghost}, docs_override={})
    assert {"ENV003", "ENV004"} <= _ids(
        _rule("env-contract").check(ctx))

    # ... and a documented name nobody declared.
    ctx = Context(REPO, files=[], full=True, env_registry={},
                  docs_override={"ZZ.md": "set RACON_TPU_ZZ_GHOST=1"})
    assert "ENV005" in _ids(_rule("env-contract").check(ctx))


# -------------------------------------------------------------- fault-site


def test_fault_site_clean(repo_findings):
    _clean(repo_findings, "fault-site")


def test_fault_site_fires():
    found = list(_rule("fault-site").check(
        _fixture_ctx("fault_violation.py")))
    assert "FLT001" in _ids(found)

    # Coverage direction: a declared site no test exercises. The name
    # is concatenated so THIS file doesn't satisfy the textual search.
    never = "zz/" + "never"
    ctx = Context(REPO, files=[], full=True, fault_sites=(never,),
                  fault_prefixes=())
    assert "FLT002" in _ids(_rule("fault-site").check(ctx))


# -------------------------------------------------------- metrics-contract


def test_metrics_contract_clean(repo_findings):
    _clean(repo_findings, "metrics-contract")


def test_metrics_contract_fires():
    found = list(_rule("metrics-contract").check(
        _fixture_ctx("metrics_violation.py")))
    assert "MET001" in _ids(found)

    # Registry directions: dead spec, undocumented spec, and a declared
    # merge kind that merge_kind() contradicts — one synthetic row
    # trips all three.
    ctx = Context(REPO, files=[], full=True,
                  metric_specs=(("zz_ghost_total", obs_metrics.MERGE_MAX,
                                 "zz_ghost_doc"),),
                  docs_override={})
    ids = _ids(_rule("metrics-contract").check(ctx))
    assert {"MET002", "MET003", "MET004"} <= ids


# ------------------------------------------------------------- span-schema


def test_span_schema_clean(repo_findings):
    _clean(repo_findings, "span-schema")


def test_span_schema_fires():
    found = list(_rule("span-schema").check(
        _fixture_ctx("span_violation.py")))
    assert {"SPAN001", "SPAN002"} <= _ids(found)
    # Both SPAN002 emits: tracer.span and timed_span.
    assert len({f.line for f in found if f.rule == "SPAN002"}) == 2

    # Validator direction: a schema kind nobody emits.
    ctx = Context(REPO, files=[], full=True,
                  span_required={"zz_ghost": ("a",)}, span_attr_free=())
    assert "SPAN003" in _ids(_rule("span-schema").check(ctx))


# ------------------------------------------------------------ atomic-write


def test_atomic_write_clean(repo_findings):
    _clean(repo_findings, "atomic-write")


def test_atomic_write_fires():
    found = list(_rule("atomic-write").check(
        _fixture_ctx("atomic_violation.py")))
    assert _ids(found) == {"ATM001"}


def test_atomic_write_pragma_suppresses(tmp_path):
    p = tmp_path / "pragma_case.py"
    p.write_text("def save(path, data):\n"
                 "    # lint: atomic-ok (test scratch file)\n"
                 "    with open(path, 'w') as fh:\n"
                 "        fh.write(data)\n")
    ctx = Context(REPO, files=[str(p)], full=False)
    assert list(_rule("atomic-write").check(ctx)) == []


# --------------------------------------------------------- lock-discipline


def test_lock_discipline_clean(repo_findings):
    _clean(repo_findings, "lock-discipline")


def test_lock_discipline_fires():
    found = list(_rule("lock-discipline").check(
        _fixture_ctx("lock_violation.py")))
    assert _ids(found) == {"LCK001"}
    # Both unguarded mutations, neither locked one.
    assert len(found) == 2
    assert all("Counter" in f.message for f in found)


# ------------------------------------------------------------- choke-point


def test_choke_point_clean(repo_findings):
    _clean(repo_findings, "choke-point")


def test_choke_point_fires():
    found = list(_rule("choke-point").check(
        _fixture_ctx("chokepoint_violation.py")))
    assert _ids(found) == {"CHK001"}


# ------------------------------------------------------------- determinism


def test_determinism_clean(repo_findings):
    _clean(repo_findings, "determinism")


def test_determinism_fires():
    found = list(_rule("determinism").check(
        _fixture_ctx("determinism_violation.py")))
    assert _ids(found) == {"DET001"}
    assert len(found) == 2  # time.time AND random.random


# --------------------------------------------------------------- histogram


def test_histogram_clean(repo_findings):
    _clean(repo_findings, "histogram")


def test_histogram_fires():
    found = list(_rule("histogram").check(
        _fixture_ctx("histogram_violation.py")))
    assert _ids(found) == {"HIS001"}

    # Registry directions: one injected family that nothing records,
    # with a 'hist' spec row but an empty corpus — the dead-producer
    # and missing-exporter directions both trip.
    ctx = Context(REPO, files=[], full=True,
                  hist_buckets={"zz_ghost_latency_s": (0.1, 1.0)},
                  metric_specs=(("zz_ghost_latency_s", "hist",
                                 "zz_ghost_doc"),))
    found = list(_rule("histogram").check(ctx))
    assert _ids(found) == {"HIS001"}
    msgs = "\n".join(f.message for f in found)
    assert "recorded nowhere" in msgs
    assert "no OpenMetrics histogram rendering" in msgs

    # A 'hist' spec row with no bounds behind it.
    ctx = Context(REPO, files=[], full=True, hist_buckets={},
                  metric_specs=(("zz_ghost_latency_s", "hist",
                                 "zz_ghost_doc"),))
    found = list(_rule("histogram").check(ctx))
    assert any("no bounds" in f.message for f in found)


def test_histogram_pragma_suppresses(tmp_path):
    p = tmp_path / "pragma_case.py"
    p.write_text("from racon_tpu.obs.metrics import record_hist\n"
                 "def observe():\n"
                 "    # lint: hist-ok (scratch family)\n"
                 "    record_hist('zz_scratch_s', 0.1)\n")
    ctx = Context(REPO, files=[str(p)], full=False)
    assert list(_rule("histogram").check(ctx)) == []


# ------------------------------------------------------- cache surface


def test_cache_surface_rules_fire():
    """The result-cache contract extensions (PR 16) are not vacuous:
    one seeded fixture trips each registry the cache surface joined —
    fault sites, metric specs, the ``cache`` span kind's attr schema,
    and the ATM001 scope over racon_tpu/cache/."""
    ctx = _fixture_ctx("cache_violation.py")
    assert "FLT001" in _ids(_rule("fault-site").check(ctx))
    assert "MET001" in _ids(_rule("metrics-contract").check(ctx))
    assert "SPAN002" in _ids(_rule("span-schema").check(ctx))
    assert "ATM001" in _ids(_rule("atomic-write").check(ctx))


def test_cache_registries_registered():
    """The registries themselves carry the cache rows: sites, metric
    specs (with the MERGE_LAST hit-ratio gauge), and the span kind."""
    from racon_tpu.resilience.faults import SITES
    assert "cache/load" in SITES and "cache/store" in SITES
    by_pattern = {p: k for p, k, _ in obs_metrics.METRIC_SPECS}
    assert by_pattern["cache_hits_total"] == obs_metrics.MERGE_SUM
    assert by_pattern["cache_hit_ratio"] == obs_metrics.MERGE_LAST
    assert obs_metrics.merge_kind("cache_hit_ratio") == \
        obs_metrics.MERGE_LAST
    assert obs_metrics.merge_kind("cache_verify_fail_total") == \
        obs_metrics.MERGE_SUM
    sys.path.insert(0, REPO)
    from scripts.obs_report import KIND_REQUIRED_ATTRS
    assert KIND_REQUIRED_ATTRS["cache"] == ("tier", "outcome")


# ------------------------------------------------------- engine mechanics


def test_reports_byte_stable():
    ctx_a, ctx_b = Context(REPO), Context(REPO)
    a = run_rules(ALL_RULES, ctx_a)
    b = run_rules(ALL_RULES, ctx_b)
    assert render_text(a) == render_text(b)
    assert render_json(a) == render_json(b)


def test_baseline_partition_and_fingerprint(tmp_path):
    f1 = Finding("ATM001", "error", "a.py", 3, "bare open")
    f2 = Finding("ATM001", "error", "b.py", 9, "bare open")
    base = tmp_path / "base.json"
    base.write_text(json.dumps([f1.fingerprint]))
    active, suppressed = split_findings([f1, f2],
                                        load_baseline(str(base)))
    assert active == [f2] and suppressed == [f1]
    # Line drift must not evict a finding from its baseline.
    drifted = Finding("ATM001", "error", "a.py", 33, "bare open")
    assert drifted.fingerprint == f1.fingerprint
    # Missing baseline file = empty baseline, not an error.
    assert load_baseline(str(tmp_path / "missing.json")) == []


def test_summary_line_format():
    f = Finding("ENV001", "error", "x.py", 1, "m")
    line = summary_line([f], [f, f], n_rules=8, n_files=101)
    assert line == ("lint_findings_total=3 active=1 baselined=2 "
                    "rules=8 files=101")


def test_lint_cli_ci_gate_passes():
    """The shipped baseline is empty and the repo lints clean, so the
    exact command ci.sh runs must exit 0 and print the summary."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--ci"], capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint_findings_total=" in proc.stdout
    assert "active=0" in proc.stdout


def test_lint_cli_ci_gate_fails_on_findings(tmp_path):
    """--ci exits 1 when a non-baselined finding exists: point the
    linter at a scratch repo containing one seeded violation."""
    scratch = tmp_path / "repo"
    (scratch / "racon_tpu").mkdir(parents=True)
    (scratch / "scripts").mkdir()
    src = open(os.path.join(FIXTURES, "determinism_violation.py")).read()
    (scratch / "racon_tpu" / "fingerprint.py").write_text(src)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--ci", "--root", str(scratch),
         "--baseline", str(tmp_path / "empty.json")],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1
    assert "DET001" in proc.stdout


# ---------------------------------------------------------------- meta


def test_every_rule_has_clean_and_fire_tests():
    """The acceptance bar: no rule ships without both a clean-on-repo
    proof and a firing-on-fixture proof in this module."""
    names = set(globals())
    missing = []
    for rule in ALL_RULES:
        slug = rule.name.replace("-", "_")
        for suffix in ("clean", "fires"):
            fn = f"test_{slug}_{suffix}"
            if fn not in names:
                missing.append(fn)
    assert missing == [], missing


def test_rule_ids_unique_and_catalogued():
    seen = {}
    for rule in ALL_RULES:
        assert rule.ids, rule.name
        for rid in rule.ids:
            assert rid not in seen, f"{rid} in {rule.name} and {seen[rid]}"
            seen[rid] = rule.name
    assert len(ALL_RULES) >= 8
    # Every rule id is documented in the catalog.
    catalog = open(os.path.join(REPO, "docs", "ANALYSIS.md")).read()
    for rid in seen:
        assert rid in catalog, f"{rid} missing from docs/ANALYSIS.md"
