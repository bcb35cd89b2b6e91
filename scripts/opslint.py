#!/usr/bin/env python
"""opslint CLI — project-specific static analysis.

Runs every analysis family in ``paddle_operator_tpu.analysis`` — the
syntactic opslint passes (OPS1xx–5xx), the interprocedural dataflow
families (OPS6xx/7xx/8xx), and the OPS001 stale-suppression audit —
over the package + scripts/ (or any paths given) and fails on findings
not recorded in the committed baseline. See docs/static-analysis.md for
the rule catalog and suppression syntax.
``scripts/analyze_all.py`` is the same engine plus the JSON report,
budget gate, and mypy/ruff stages (what ``make analyze`` runs).

    python scripts/opslint.py                      # lint the project
    python scripts/opslint.py --list-rules
    python scripts/opslint.py --update-baseline    # accept current findings
    python scripts/opslint.py --prune-baseline     # drop stale entries
    python scripts/opslint.py paddle_operator_tpu/ps.py --no-baseline
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_operator_tpu.analysis import engine, opslint  # noqa: E402

REPO = engine.REPO_ROOT
DEFAULT_BASELINE = os.path.join(REPO, "opslint_baseline.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="project-specific lint")
    ap.add_argument("paths", nargs="*",
                    help="files/trees to lint (default: package + "
                         "scripts/)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, baselined or not")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="rewrite the baseline keeping only entries a "
                         "live finding still matches")
    ap.add_argument("--rules", default="",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, (name, desc) in sorted(engine.ALL_RULES.items()):
            print("%s  %-28s %s" % (rid, name, desc))
        return 0

    rules = [r.strip() for r in args.rules.split(",") if r.strip()] or None
    paths = args.paths or engine.default_paths()
    findings = engine.run_all(paths, root=REPO,
                              axis_paths=engine.axis_paths(), rules=rules)

    if args.update_baseline:
        opslint.write_baseline(findings, args.baseline)
        print("opslint: baseline updated: %d finding(s) accepted in %s"
              % (len(findings), os.path.relpath(args.baseline, REPO)))
        return 0
    if args.prune_baseline:
        kept, total = engine.prune_baseline(findings, args.baseline,
                                            scope=paths, root=REPO)
        print("opslint: baseline pruned: %d of %d entrie(s) kept"
              % (kept, total))
        return 0

    baseline = ({} if args.no_baseline
                else opslint.load_baseline(args.baseline))
    new, accepted = opslint.apply_baseline(findings, baseline)
    # stale baseline fingerprints are findings in their own right
    # (OPS001): the baseline can only shrink. Judged only inside the
    # analyzed scope, and never under a --rules subset.
    new.extend(engine.stale_baseline_findings(
        findings, baseline, args.baseline, scope=paths, root=REPO,
        rules=rules))
    new.sort(key=lambda f: (f.path, f.line, f.rule, f.symbol, f.message))
    for f in new:
        print(f.render())
    if accepted:
        print("opslint: %d baselined finding(s) suppressed" % len(accepted))
    if new:
        print("opslint: %d new finding(s)" % len(new))
        return 1
    print("opslint: clean (%d finding(s), all baselined)"
          % len(accepted) if accepted else "opslint: clean")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # | head etc. closing stdout is not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
