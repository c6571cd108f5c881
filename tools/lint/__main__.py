"""CLI: ``python -m tools.lint paddle_tpu/ [options]``.

Exit status 0 iff zero UNBASELINED findings (the CI bar). Common runs::

    python -m tools.lint paddle_tpu/                 # the gate
    python -m tools.lint paddle_tpu/ --summary       # per-checker table
    python -m tools.lint paddle_tpu/serving/         # one subtree
    python -m tools.lint paddle_tpu/ --fix-baseline  # re-triage: rewrite
        # baseline.json keeping justifications of surviving entries;
        # NEW entries get an UNREVIEWED placeholder you must replace
    python -m tools.lint paddle_tpu/ --no-baseline   # everything, raw
"""
from __future__ import annotations

import argparse
import os
import sys
from collections import Counter

from .core import (BaselineError, apply_baseline, covered_relfiles,
                   default_baseline_path, generate_baseline, lint_paths,
                   load_baseline, write_baseline)
from .checks import CHECKERS


def _summary(findings, suppressed, stale, top: int = 8) -> str:
    lines = ["paddle_tpu-lint summary", "=" * 23, "",
             f"{'checker':<8} {'new':>5} {'baselined':>10}"]
    new_c = Counter(f.checker for f in findings)
    sup_c = Counter(f.checker for f in suppressed)
    for cid in sorted(set(CHECKERS) | set(new_c) | set(sup_c)):
        lines.append(f"{cid:<8} {new_c.get(cid, 0):>5} "
                     f"{sup_c.get(cid, 0):>10}")
    lines.append(f"{'total':<8} {sum(new_c.values()):>5} "
                 f"{sum(sup_c.values()):>10}")
    files = Counter(f.file for f in findings)
    if files:
        lines += ["", f"top files (new findings):"]
        for path, n in files.most_common(top):
            lines.append(f"  {n:>4}  {path}")
    if stale:
        lines += ["", f"stale baseline entries (nothing matches them "
                      f"anymore — prune with --fix-baseline): "
                      f"{len(stale)}"]
        for fp in stale[:top]:
            lines.append(f"  {fp}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="invariant-aware static analysis for paddle_tpu "
                    "(PT001 recompile / PT002 host-sync / PT003 series "
                    "lifecycle / PT004 lock discipline / PT005 flag "
                    "gating / PT006 socket I/O / PT007 eager dispatch)")
    ap.add_argument("paths", nargs="+", help="files/dirs to lint")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: tools/lint/"
                         "baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, suppressing nothing")
    ap.add_argument("--fix-baseline", action="store_true",
                    help="rewrite the baseline from the CURRENT "
                         "findings, keeping justifications of entries "
                         "that still match; new entries get an "
                         "UNREVIEWED placeholder to replace")
    ap.add_argument("--summary", action="store_true",
                    help="per-checker counts + top files "
                         "(monitor_report-style)")
    ap.add_argument("--checks", default=None,
                    help="comma-separated subset, e.g. PT001,PT003")
    args = ap.parse_args(argv)

    checks = (None if args.checks is None
              else [c.strip().upper() for c in args.checks.split(",")])
    if checks is not None:
        unknown = [c for c in checks if c not in CHECKERS]
        if unknown:
            print(f"unknown checker id(s): {', '.join(unknown)} "
                  f"(known: {', '.join(sorted(CHECKERS))})",
                  file=sys.stderr)
            return 2

    findings = lint_paths(args.paths, root=os.getcwd(), checks=checks)
    covered = covered_relfiles(args.paths, root=os.getcwd())

    baseline_path = args.baseline or default_baseline_path()
    baseline = {}
    if not args.no_baseline and os.path.exists(baseline_path):
        try:
            baseline = load_baseline(baseline_path)
        except BaselineError as e:
            print(f"baseline error: {e}", file=sys.stderr)
            return 2

    if args.fix_baseline:
        # regeneration always starts from the ON-DISK baseline (even
        # under --no-baseline) and keeps entries outside this run's
        # scope: a subtree or --checks regeneration must not delete
        # suppressions — or their justifications — it never re-examined
        previous = baseline
        if not previous and os.path.exists(baseline_path):
            try:
                previous = load_baseline(baseline_path)
            except BaselineError as e:
                print(f"baseline error: {e}", file=sys.stderr)
                return 2
        doc = generate_baseline(findings, previous=previous,
                                covered_files=covered,
                                covered_checks=checks)
        write_baseline(doc, baseline_path)
        unreviewed = sum(
            1 for e in doc["entries"]
            if e["justification"].startswith("UNREVIEWED"))
        print(f"wrote {baseline_path}: {len(doc['entries'])} entries "
              f"({unreviewed} UNREVIEWED — replace the placeholders "
              "before committing)")
        return 0

    new, suppressed, stale = apply_baseline(
        findings, baseline, covered_files=covered,
        covered_checks=checks)

    if args.summary:
        print(_summary(new, suppressed, stale))
        if new:
            print()
    for f in new:
        print(f.render())
    if not args.summary:
        if suppressed:
            print(f"[{len(suppressed)} baselined finding(s) suppressed "
                  f"by {os.path.relpath(baseline_path)}]")
        if stale:
            print(f"[{len(stale)} stale baseline entrie(s) — prune "
                  "with --fix-baseline]")
    if new:
        print(f"\n{len(new)} unbaselined finding(s). The bar is zero: "
              "fix them, annotate the blessed idiom, or triage into "
              "the baseline WITH a justification (--fix-baseline "
              "writes the skeleton).", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
