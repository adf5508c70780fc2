"""mxtpu-lint command line: human and JSON reports, baseline workflow.

Exit codes: 0 clean (all findings baselined or none), 1 new findings
or parse errors, 2 usage errors.  ``--json`` emits one machine-readable
document (finding counts per checker).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .core import (all_checkers, apply_baseline, load_baseline, run_lint,
                   save_baseline)

DEFAULT_BASELINE = os.path.join("tools", "lint_baseline.json")


def counts_by_check(findings):
    out = {}
    for f in findings:
        out[f.check] = out.get(f.check, 0) + 1
    return out


def build_parser():
    p = argparse.ArgumentParser(
        prog="mxtpu_lint",
        description="JAX-aware static analysis for mxnet_tpu "
                    "(see docs/how_to/static_analysis.md)")
    p.add_argument("paths", nargs="*", default=None,
                   help="files/directories to lint "
                        "(default: mxnet_tpu tools, relative to --repo)")
    p.add_argument("--repo", default=None,
                   help="repo root (default: parent of this tool)")
    p.add_argument("--checks", default=None,
                   help="comma-separated checker ids to run "
                        "(default: all)")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON path (default: "
                        f"{DEFAULT_BASELINE} under --repo when it "
                        "exists; 'none' disables)")
    p.add_argument("--write-baseline", action="store_true",
                   help="write current findings to --baseline and exit "
                        "0 (the burn-down starting point)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit a JSON report instead of text")
    p.add_argument("--list-checks", action="store_true",
                   help="list checker ids with their rationale")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.list_checks:
        for cid, cls in sorted(all_checkers().items()):
            doc = " ".join((cls.doc or "").split())
            print(f"{cid}\n    {doc}\n")
        return 0

    repo = args.repo or os.getcwd()
    paths = args.paths or [os.path.join(repo, "mxnet_tpu"),
                           os.path.join(repo, "tools")]
    checks = [c.strip() for c in args.checks.split(",")] \
        if args.checks else None
    try:
        findings, errors = run_lint(paths, repo=repo, checks=checks)
    except ValueError as e:
        print(f"mxtpu-lint: {e}", file=sys.stderr)
        return 2

    baseline_path = args.baseline
    if baseline_path is None:
        cand = os.path.join(repo, DEFAULT_BASELINE)
        baseline_path = cand if os.path.exists(cand) else "none"
    elif baseline_path != "none" and not os.path.isabs(baseline_path):
        baseline_path = os.path.join(repo, baseline_path)

    if args.write_baseline:
        if baseline_path == "none":
            baseline_path = os.path.join(repo, DEFAULT_BASELINE)
        save_baseline(baseline_path, findings)
        print(f"baseline written: {baseline_path} "
              f"({len(findings)} finding(s))")
        return 0

    baseline = load_baseline(baseline_path) \
        if baseline_path != "none" else {}
    new, baselined, stale = apply_baseline(findings, baseline)

    if args.as_json:
        doc = {"findings": [f.to_dict() for f in new],
               "baselined": len(baselined),
               "stale_baseline_entries": [list(k) for k in stale],
               "errors": [{"path": p, "message": m} for p, m in errors],
               "counts": counts_by_check(new),
               "counts_all": counts_by_check(findings),
               "checks": sorted(all_checkers() if not checks
                                else checks),
               "clean": not new and not errors}
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0 if doc["clean"] else 1

    for path, msg in errors:
        print(f"{path}: ERROR {msg}", file=sys.stderr)
    for f in new:
        print(f.render())
        if f.code:
            print(f"    {f.code}")
    if stale:
        print(f"note: {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} matched nothing — "
              "delete them:", file=sys.stderr)
        for check, path, code in stale:
            print(f"    [{check}] {path}: {code}", file=sys.stderr)
    if new or errors:
        by = counts_by_check(new)
        summary = ", ".join(f"{k}={v}" for k, v in sorted(by.items()))
        print(f"mxtpu-lint: {len(new)} new finding(s) "
              f"({summary or 'parse errors only'}), "
              f"{len(baselined)} baselined, {len(errors)} error(s)",
              file=sys.stderr)
        return 1
    if baselined:
        print(f"mxtpu-lint: clean — 0 new findings, "
              f"{len(baselined)} baselined")
    else:
        print("mxtpu-lint: clean — 0 findings")
    return 0
