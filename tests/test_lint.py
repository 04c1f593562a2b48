"""Tier-1 gate for graftlint (hydragnn_tpu/analysis, tools/graftlint.py).

The contract (ISSUE 9, docs/ANALYSIS.md):

- the FULL rule suite over hydragnn_tpu/, tools/ and tests/ reports
  zero unsuppressed, unbaselined findings — a PR that introduces a new
  violation fails here with the rendered finding in the assert message;
- every rule's fixture corpus passes (the analyzer is tested, not just
  its current verdict on the tree);
- seeding a lock-coverage violation into a fixture copy of
  serve/batcher.py is detected (the acceptance probe);
- the knob and health-kind registries are exhaustive against
  grep/AST-extracted ground truth, and docs/KNOBS.md matches the
  generated table;
- suppression, baseline, and diff-scoping mechanics behave.

Keep this module free of undeclared ``HYDRAGNN_*`` string literals and
broad silent excepts — it lints itself.
"""

from __future__ import annotations

import ast
import json
import os
import re
import subprocess
import sys

import pytest

from hydragnn_tpu.analysis import (
    HEALTH_KINDS,
    KNOBS,
    Severity,
    all_rules,
    collect_project,
    emit_knob_docs,
    load_baseline,
    run_project,
)
from hydragnn_tpu.analysis.project import parse_file
from hydragnn_tpu.analysis.runner import BaselineEntry
from hydragnn_tpu.analysis.selftest import run_selftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_PATHS = [os.path.join(REPO, p)
              for p in ("hydragnn_tpu", "tools", "tests")]


# -- the gate ---------------------------------------------------------------

def test_tree_is_clean():
    """THE tier-1 invariant: zero unsuppressed findings over the tree."""
    project = collect_project(REPO, SCAN_PATHS)
    baseline = load_baseline(
        os.path.join(REPO, "tools", "graftlint_baseline.json"))
    result = run_project(project, baseline=baseline)
    rendered = "\n".join(f.render() for f in result.findings)
    assert not result.findings, (
        f"graftlint found {len(result.findings)} new violation(s) — fix "
        f"them, suppress with a justified `# graftlint: disable=RULE "
        f"(reason)`, or (only if provably benign) baseline them:\n"
        f"{rendered}")
    # the baseline must stay free of dead entries
    assert not result.stale_baseline, (
        "stale graftlint baseline entries (the findings are gone): "
        + ", ".join(f"{e.rule}@{e.path}" for e in result.stale_baseline))


def test_baseline_entries_are_justified():
    baseline = load_baseline(
        os.path.join(REPO, "tools", "graftlint_baseline.json"))
    bad = [e for e in baseline
           if not e.justification or e.justification.startswith("TODO")]
    assert not bad, (
        "every baseline entry needs a real one-line justification: "
        + ", ".join(f"{e.rule}@{e.path}" for e in bad))


# -- the analyzer is tested, not just its verdict ---------------------------

def test_rule_fixtures_selftest():
    ok, report = run_selftest()
    assert ok, "rule-fixture selftest failed:\n" + "\n".join(
        line for line in report if line.startswith("FAIL"))


def test_every_rule_has_fixture_coverage():
    """A new rule must ship fixtures (PER_FILE or a special-case harness
    in selftest.py) — adding a rule id without selftest coverage fails."""
    from hydragnn_tpu.analysis.selftest import PER_FILE_RULES, PROJECT_RULES

    covered = set(PER_FILE_RULES) | set(PROJECT_RULES)
    missing = {r.id for r in all_rules()} - covered
    assert not missing, f"rules without selftest coverage: {missing}"


def test_seeded_batcher_lock_violation_detected(tmp_path):
    """Acceptance probe: an unguarded write to a locked class's shared
    attribute seeded into a copy of serve/batcher.py is caught."""
    src = open(os.path.join(
        REPO, "hydragnn_tpu", "serve", "batcher.py")).read()
    anchor = '    def start(self) -> "MicroBatcher":'
    assert anchor in src
    seeded = src.replace(anchor, (
        "    def _seeded_violation(self):\n"
        "        self._fill_sum = 0.0\n\n" + anchor), 1)
    p = tmp_path / "batcher_seeded.py"
    p.write_text(seeded)
    ctx = parse_file(str(p), root=str(tmp_path))
    lck = next(r for r in all_rules() if r.id == "LCK001")
    found = [f for f in lck.check_file(ctx)
             if "_seeded_violation" in f.message]
    assert found, "seeded unguarded write was NOT detected"
    assert "_fill_sum" in found[0].message
    # and the pristine copy stays clean (the seeding is what's detected)
    clean_ctx = parse_file(os.path.join(
        REPO, "hydragnn_tpu", "serve", "batcher.py"), root=REPO)
    assert not list(lck.check_file(clean_ctx))


# -- registry exhaustiveness (acceptance criteria) --------------------------

def _iter_repo_py():
    for top in SCAN_PATHS:
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "fixtures")]
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def test_knob_registry_exhaustive():
    """Grep-extracted HYDRAGNN_* names are a subset of the declared
    registry, and every declared knob is documented in docs/KNOBS.md."""
    knob_re = re.compile(r"HYDRAGNN_[A-Z0-9_]+")
    used = set()
    for path in _iter_repo_py():
        if path.endswith(os.path.join("analysis", "registry.py")):
            continue
        for m in knob_re.findall(open(path, encoding="utf-8").read()):
            if not m.endswith("_"):  # prefix constructions are not knobs
                used.add(m)
    undeclared = used - set(KNOBS)
    assert not undeclared, f"undeclared env knobs in code: {undeclared}"
    docs = open(os.path.join(REPO, "docs", "KNOBS.md"),
                encoding="utf-8").read()
    undocumented = {k for k in KNOBS if f"`{k}`" not in docs}
    assert not undocumented, f"knobs missing from docs/KNOBS.md: {undocumented}"


def test_knob_docs_generated_current():
    on_disk = open(os.path.join(REPO, "docs", "KNOBS.md"),
                   encoding="utf-8").read()
    assert on_disk == emit_knob_docs(), (
        "docs/KNOBS.md is stale — regenerate with "
        "`python tools/graftlint.py --emit-docs`")


def test_aggr_backend_knob_has_one_reader():
    """The aggregation-path decision is read in ONE place.  An
    ``os.environ`` access needs the knob's name as a whole string constant
    (prose mentions are longer strings): under hydragnn_tpu/ only
    ops/aggregate.py holds one, and that module is what asks
    ``os.environ``."""
    knob = "HYDRAGNN_AGGR_BACKEND"
    assert knob in KNOBS
    holders = {}
    for path in _iter_repo_py():
        if (f"{os.sep}hydragnn_tpu{os.sep}" not in path
                or path.endswith(os.path.join("analysis", "registry.py"))):
            continue
        tree = ast.parse(open(path, encoding="utf-8").read())
        if any(isinstance(n, ast.Constant) and n.value == knob
               for n in ast.walk(tree)):
            holders[os.path.relpath(path, REPO)] = tree
    reader = os.path.join("hydragnn_tpu", "ops", "aggregate.py")
    assert sorted(holders) == [reader], (
        f"{knob} is named outside {reader}: ask aggr_backend() / "
        f"backend_scope() there instead — {sorted(holders)}")
    assert any(isinstance(n, ast.Attribute) and n.attr == "environ"
               and isinstance(n.value, ast.Name) and n.value.id == "os"
               for n in ast.walk(holders[reader]))


def test_health_kind_registry_exhaustive():
    """AST-extracted health(kind=...) literals are a subset of the
    declared registry; every declared kind is documented and emitted."""
    emitted = set()
    for path in _iter_repo_py():
        if f"{os.sep}hydragnn_tpu{os.sep}" not in path:
            continue
        tree = ast.parse(open(path, encoding="utf-8").read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if name != "health":
                continue
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                emitted.add(a.value)
            elif isinstance(a, ast.IfExp):
                for b in (a.body, a.orelse):
                    if isinstance(b, ast.Constant):
                        emitted.add(b.value)
    undeclared = emitted - set(HEALTH_KINDS)
    assert not undeclared, f"undeclared health kinds: {undeclared}"
    dead = set(HEALTH_KINDS) - emitted
    assert not dead, f"declared-but-never-emitted health kinds: {dead}"
    docs = open(os.path.join(REPO, "docs", "TELEMETRY.md"),
                encoding="utf-8").read()
    undocumented = {k for k in HEALTH_KINDS if f"`{k}`" not in docs}
    assert not undocumented, (
        f"health kinds missing from docs/TELEMETRY.md: {undocumented}")


# -- mechanics --------------------------------------------------------------

_VIOLATING = (
    "import time\n"
    "import jax\n\n\n"
    "@jax.jit\n"
    "def step(x):\n"
    "    return x + time.time()\n"
)


def test_suppression_mechanics(tmp_path):
    p = tmp_path / "v.py"
    p.write_text(_VIOLATING)
    project = collect_project(str(tmp_path), [str(tmp_path)])
    result = run_project(project)
    assert any(f.rule == "TRC001" for f in result.findings)

    p.write_text(_VIOLATING.replace(
        "    return x + time.time()\n",
        "    return x + time.time()  "
        "# graftlint: disable=TRC001 (test)\n"))
    project = collect_project(str(tmp_path), [str(tmp_path)])
    result = run_project(project)
    assert not [f for f in result.findings if f.rule == "TRC001"]
    assert any(f.rule == "TRC001" for f in result.suppressed)


def test_baseline_survives_line_drift(tmp_path):
    p = tmp_path / "v.py"
    p.write_text(_VIOLATING)
    project = collect_project(str(tmp_path), [str(tmp_path)])
    finding = next(f for f in run_project(project).findings
                   if f.rule == "TRC001")
    entry = BaselineEntry(rule=finding.rule, path=finding.path,
                          code=finding.code, justification="test entry")
    # shift the violation down two lines: the entry still matches
    p.write_text("# pad\n# pad\n" + _VIOLATING)
    project = collect_project(str(tmp_path), [str(tmp_path)])
    result = run_project(project, baseline=[entry])
    assert not [f for f in result.findings if f.rule == "TRC001"]
    assert any(f.rule == "TRC001" for f in result.baselined)
    assert not result.stale_baseline


def test_diff_scoping(tmp_path):
    p = tmp_path / "v.py"
    p.write_text(_VIOLATING)
    project = collect_project(str(tmp_path), [str(tmp_path)])
    line = next(f for f in run_project(project).findings
                if f.rule == "TRC001").line
    # finding's line not in the changed set -> scoped out
    scoped = run_project(project, changed={"v.py": {1}})
    assert not scoped.findings
    scoped = run_project(project, changed={"v.py": {line}})
    assert any(f.rule == "TRC001" for f in scoped.findings)


def test_severity_ordering_and_parse():
    assert Severity.parse("error") > Severity.parse("warn") > \
        Severity.parse("note")
    with pytest.raises(ValueError):
        Severity.parse("fatal")


# -- CLI contract -----------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
         *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cli_exit_codes_and_json(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    r = _run_cli(str(clean))
    assert r.returncode == 0, r.stdout + r.stderr

    dirty = tmp_path / "dirty.py"
    dirty.write_text(_VIOLATING)
    r = _run_cli(str(dirty), "--json")
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["counts"]["findings"] >= 1
    assert any(f["rule"] == "TRC001" for f in doc["findings"])
    assert all({"rule", "severity", "path", "line", "message",
                "fingerprint"} <= set(f) for f in doc["findings"])

    r = _run_cli(str(tmp_path / "missing.py"))
    assert r.returncode == 2  # usage error contract

    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rule in all_rules():
        assert rule.id in r.stdout


def test_cli_loads_without_jax(tmp_path):
    """The CLI's whole point: a lint pass must not pay the jax import
    (dependency-free stdlib ast only)."""
    cli = os.path.join(REPO, "tools", "graftlint.py")
    probe = (
        "import sys\n"
        "sys.argv = ['graftlint', '--list-rules']\n"
        f"g = {{'__name__': '__main__', '__file__': {cli!r}}}\n"
        "try:\n"
        f"    exec(compile(open({cli!r}).read(), {cli!r}, 'exec'), g)\n"
        "except SystemExit as e:\n"
        "    assert (e.code or 0) == 0, e.code\n"
        "assert 'jax' not in sys.modules, 'graftlint imported jax!'\n"
    )
    p = tmp_path / "probe.py"
    p.write_text(probe)
    r = subprocess.run([sys.executable, str(p)], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
