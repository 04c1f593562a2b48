#!/usr/bin/env python3
"""graftlint — the repo's project-invariant static-analysis gate.

Usage:
    python tools/graftlint.py [paths ...]         # default: hydragnn_tpu tools tests
    python tools/graftlint.py --json              # machine-readable findings
    python tools/graftlint.py --diff [REF]        # only findings on lines changed vs REF (default HEAD)
    python tools/graftlint.py --selftest          # run the rule fixtures
    python tools/graftlint.py --emit-docs         # regenerate docs/KNOBS.md and docs/TELEMETRY.md's trace name tables from the registry
    python tools/graftlint.py --write-baseline    # grandfather current findings (justify each entry!)
    python tools/graftlint.py --list-rules        # rule catalog one-liners

Exit codes: 0 = clean (no unsuppressed, unbaselined findings),
1 = findings, 2 = usage/internal error.

Dependency-free (stdlib only): the analysis package is loaded standalone
so a lint pass never pays the jax import.  docs/ANALYSIS.md is the rule
catalog; tests/test_lint.py runs the same gate in tier-1.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_analysis():
    """Import hydragnn_tpu/analysis WITHOUT triggering the package
    __init__ of hydragnn_tpu (which imports jax)."""
    pkg_dir = os.path.join(ROOT, "hydragnn_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        "graftlint_analysis", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["graftlint_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    default=["hydragnn_tpu", "tools", "tests"])
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--diff", nargs="?", const="HEAD", default=None,
                    metavar="REF")
    ap.add_argument("--baseline",
                    default=os.path.join("tools",
                                         "graftlint_baseline.json"))
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--emit-docs", action="store_true")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--rules", default="",
                    help="comma list of rule ids to run (default: all)")
    ap.add_argument("--min-severity", default="note",
                    choices=["note", "warn", "error"])
    args = ap.parse_args(argv)

    try:
        a = _load_analysis()
    except Exception as e:
        print(f"graftlint: failed to load analysis package: {e}",
              file=sys.stderr)
        return 2

    if args.list_rules:
        for r in a.all_rules():
            print(f"{r.id}  {r.name}  [{r.severity.name.lower()}]  "
                  f"{r.doc}")
        return 0

    if args.selftest:
        from graftlint_analysis.selftest import run_selftest

        ok, report = run_selftest()
        print("\n".join(report))
        print(f"selftest: {'OK' if ok else 'FAILED'}")
        return 0 if ok else 1

    if args.emit_docs:
        out = os.path.join(ROOT, "docs", "KNOBS.md")
        with open(out, "w", encoding="utf-8") as fh:
            fh.write(a.emit_knob_docs())
        print(f"wrote {os.path.relpath(out, ROOT)} "
              f"({len(a.KNOBS)} knobs)")
        # the region / scope / kernel name tables of "Tracing"
        out = os.path.join(ROOT, "docs", "TELEMETRY.md")
        with open(out, encoding="utf-8") as fh:
            doc = fh.read()
        i, j = doc.find(a.TRACE_DOC_BEGIN), doc.find(a.TRACE_DOC_END)
        if i < 0 or j < 0:
            print(f"graftlint: {os.path.relpath(out, ROOT)} has no "
                  f"generated-names block to rewrite", file=sys.stderr)
            return 2
        with open(out, "w", encoding="utf-8") as fh:
            fh.write(doc[:i] + a.emit_trace_docs().rstrip("\n")
                     + doc[j + len(a.TRACE_DOC_END):])
        print(f"wrote the trace name tables of "
              f"{os.path.relpath(out, ROOT)}")
        return 0

    rules = a.all_rules()
    if args.rules:
        want = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = want - {r.id for r in rules}
        if unknown:
            print(f"graftlint: unknown rule id(s): "
                  f"{', '.join(sorted(unknown))}", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.id in want]

    t0 = time.time()
    try:
        paths = [p if os.path.isabs(p) else os.path.join(ROOT, p)
                 for p in args.paths]
        for p in paths:
            if not os.path.exists(p):
                print(f"graftlint: no such path: {p}", file=sys.stderr)
                return 2
        project = a.collect_project(ROOT, paths)
        baseline_path = (args.baseline if os.path.isabs(args.baseline)
                         else os.path.join(ROOT, args.baseline))
        baseline = a.load_baseline(baseline_path)
        changed = None
        if args.diff is not None:
            import subprocess

            from graftlint_analysis.runner import changed_lines_from_git

            try:
                changed = changed_lines_from_git(ROOT, args.diff)
            except subprocess.CalledProcessError as e:
                print(f"graftlint: git diff {args.diff!r} failed: "
                      f"{(e.stderr or '').strip()}", file=sys.stderr)
                return 2
        result = a.run_project(project, rules=rules, baseline=baseline,
                               changed=changed)
    except SyntaxError as e:
        print(f"graftlint: syntax error in scanned file: {e}",
              file=sys.stderr)
        return 2
    dt = time.time() - t0

    if args.write_baseline:
        # matching universe = new findings AND currently-baselined ones
        # (kept entries must match SOMETHING or they are shed as stale)
        a.write_baseline(baseline_path,
                         list(result.findings) + list(result.baselined),
                         keep=baseline)
        print(f"wrote {os.path.relpath(baseline_path, ROOT)} "
              f"({len(result.findings)} new entries — justify each!)")
        return 0

    min_sev = a.Severity.parse(args.min_severity)
    shown = [f for f in result.findings if f.severity >= min_sev]

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_json() for f in shown],
            "counts": {
                "findings": len(result.findings),
                "suppressed": len(result.suppressed),
                "baselined": len(result.baselined),
                "stale_baseline": len(result.stale_baseline),
                "files": result.files_scanned,
            },
            "elapsed_s": round(dt, 3),
        }, indent=1))
    else:
        for f in shown:
            print(f.render())
        for e in result.stale_baseline:
            print(f"stale baseline entry {e.rule} @ {e.path} "
                  f"({e.code[:60]!r}) — the finding is gone; run "
                  f"--write-baseline (or delete the entry)")
        print(f"graftlint: {len(result.findings)} finding(s), "
              f"{len(result.suppressed)} suppressed, "
              f"{len(result.baselined)} baselined, "
              f"{len(result.stale_baseline)} stale baseline, "
              f"{result.files_scanned} files in {dt:.2f}s")
    # stale baseline entries fail too — the CLI and the tier-1 gate
    # (tests/test_lint.py) must agree on what "clean" means
    return 1 if (result.findings or result.stale_baseline) else 0


if __name__ == "__main__":
    sys.exit(main())
