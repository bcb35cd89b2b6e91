#!/usr/bin/env python
"""Single static-analysis entry point (``make analyze``).

Runs every analysis family over the project — the syntactic opslint
passes (OPS1xx–5xx), the interprocedural dataflow families (OPS6xx
buffer ownership/donation, OPS7xx mesh consistency, OPS8xx blocking
transfers), the OPS001 stale-suppression audit, and mypy/ruff when
installed — then emits a machine-readable JSON findings report and
enforces a wall-clock budget so the analysis stage stays fast enough to
sit inside ``make verify``.

    python scripts/analyze_all.py                    # full gate
    python scripts/analyze_all.py --changed          # git-diff scope
    python scripts/analyze_all.py --list-rules
    python scripts/analyze_all.py --out report.json
    python scripts/analyze_all.py --prune-baseline   # drop stale entries

``--changed [REF]`` is the pre-commit lane: the whole tree is still
parsed and summarized (interprocedural findings need the full call
graph), but only files changed vs REF (default HEAD; plus untracked)
are re-reported — identical findings on those files to a full run,
asserted in-suite. Baseline-staleness and the mypy/ruff stages are
skipped (a partial report has no opinion on the rest of the tree).

Exit: 1 on any non-baselined finding (stale pragmas and stale baseline
entries included), or on budget overrun.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_operator_tpu.analysis import engine, opslint  # noqa: E402

# analysis scope (engine.default_paths): the package and the operational
# scripts — the two trees production code ships from; tests/ and
# examples/ contribute mesh-axis vocabulary only
REPO = engine.REPO_ROOT
DEFAULT_BASELINE = os.path.join(REPO, "opslint_baseline.json")


def _run_optional_tool(module: str, args, findings_out, repo=REPO):
    """mypy/ruff gate when installed; absence degrades to a notice (the
    CI image does not bake them in)."""
    try:
        __import__(module)
    except ImportError:
        print("analyze: %s not installed; skipping (config in "
              "pyproject.toml)" % module)
        return 0
    proc = subprocess.run([sys.executable, "-m"] + args, cwd=repo,
                          capture_output=True, text=True)
    if proc.stdout:
        sys.stdout.write(proc.stdout)
    if proc.stderr:
        sys.stderr.write(proc.stderr)
    # best-effort line parse into the report ("path:line: message")
    for line in proc.stdout.splitlines():
        parts = line.split(":", 3)
        if len(parts) >= 3 and parts[1].strip().isdigit():
            findings_out.append({
                "tool": module,
                "rule": module,
                "file": parts[0].strip(),
                "line": int(parts[1].strip()),
                "fingerprint": "",
                "message": parts[-1].strip(),
            })
    return proc.returncode


def changed_files(repo=REPO, ref="HEAD"):
    """Repo-relative .py files changed vs ``ref`` (worktree, staged,
    and untracked). Empty set on a clean tree; None when git is
    unavailable (callers fall back to a full run)."""
    out = set()
    for args in (["git", "diff", "--name-only", ref, "--"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            proc = subprocess.run(args, cwd=repo, capture_output=True,
                                  text=True)
        except OSError:
            return None
        if proc.returncode != 0:
            return None
        out.update(line.strip() for line in proc.stdout.splitlines()
                   if line.strip())
    return {f for f in out if f.endswith(".py")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="all static-analysis families + JSON report")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/trees to analyze (default: package + "
                         "scripts/)")
    ap.add_argument("--changed", nargs="?", const="HEAD", default=None,
                    metavar="REF",
                    help="incremental mode: report findings only for "
                         "files changed vs REF (default HEAD) plus "
                         "untracked files, over the full shared parse "
                         "— the pre-commit lane")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="rewrite the baseline dropping stale entries")
    ap.add_argument("--rules", default="",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--out", default="",
                    help="write the JSON findings report here "
                         "(default: build/analysis_report.json)")
    ap.add_argument("--budget-seconds", type=float,
                    default=float(os.environ.get(
                        "TPUJOB_ANALYZE_BUDGET", "30")),
                    help="fail when the opslint+dataflow stage exceeds "
                         "this wall-clock budget (0 disables)")
    ap.add_argument("--skip-tools", action="store_true",
                    help="skip the mypy/ruff stages (pure "
                         "opslint+dataflow run)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, (name, desc) in sorted(engine.ALL_RULES.items()):
            print("%s  %-28s %s" % (rid, name, desc))
        return 0

    rules = [r.strip() for r in args.rules.split(",") if r.strip()] or None
    paths = args.paths or engine.default_paths()

    if args.changed is not None and (args.update_baseline
                                     or args.prune_baseline):
        # a partial report would rewrite the baseline as if every
        # finding elsewhere had vanished
        print("analyze: --changed cannot combine with baseline rewrites")
        return 2

    report_paths = None
    if args.changed is not None:
        changed = changed_files(ref=args.changed)
        if changed is None:
            print("analyze: --changed: git unavailable; running full")
        else:
            report_paths = {f for f in changed
                            if engine._in_scope(f, paths, REPO)}
            if not report_paths:
                print("analyze: --changed: no changed files in scope "
                      "(vs %s); clean" % args.changed)
                return 0
            print("analyze: --changed: reporting %d file(s): %s"
                  % (len(report_paths),
                     ", ".join(sorted(report_paths))))

    t0 = time.perf_counter()
    findings = engine.run_all(paths, root=REPO,
                              axis_paths=engine.axis_paths(), rules=rules,
                              report_paths=report_paths)
    elapsed = time.perf_counter() - t0

    if args.update_baseline or args.prune_baseline:
        if args.prune_baseline:
            kept, total = engine.prune_baseline(
                findings, args.baseline, scope=paths, root=REPO)
            print("analyze: baseline pruned: %d of %d entrie(s) kept"
                  % (kept, total))
        else:
            opslint.write_baseline(findings, args.baseline)
            print("analyze: baseline updated: %d finding(s) accepted"
                  % len(findings))
        return 0

    baseline = ({} if args.no_baseline
                else opslint.load_baseline(args.baseline))
    new, accepted = opslint.apply_baseline(findings, baseline)
    # a --changed run reports a slice of the tree: it has no opinion on
    # whether baseline entries elsewhere went stale
    stale = [] if report_paths is not None else \
        engine.stale_baseline_findings(
            findings, baseline, args.baseline, scope=paths, root=REPO,
            rules=rules)
    new.extend(stale)
    new.sort(key=lambda f: (f.path, f.line, f.rule, f.symbol, f.message))

    report = {
        "elapsed_seconds": round(elapsed, 3),
        "budget_seconds": args.budget_seconds,
        "baselined": len(accepted),
        "findings": [
            {
                "tool": engine.family_of(f.rule),
                "rule": f.rule,
                "file": f.path,
                "line": f.line,
                "fingerprint": f.fingerprint(),
                "message": f.message,
                "symbol": f.symbol,
            }
            for f in new
        ],
    }

    rc = 0
    if not args.skip_tools and report_paths is None:
        rc |= _run_optional_tool("mypy", [
            "mypy", "paddle_operator_tpu/api", "paddle_operator_tpu/analysis",
            "paddle_operator_tpu/sched", "paddle_operator_tpu/obs",
            "paddle_operator_tpu/serving", "paddle_operator_tpu/artifacts",
            "scripts",
        ], report["findings"]) and 1
        rc |= _run_optional_tool("ruff", [
            "ruff", "check", "paddle_operator_tpu", "scripts",
        ], report["findings"]) and 1

    out_path = args.out or os.path.join(REPO, "build",
                                        "analysis_report.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
            fh.write("\n")
    except OSError as e:
        print("analyze: WARNING could not write report %s: %s"
              % (out_path, e))

    for f in new:
        print(f.render())
    if accepted:
        print("analyze: %d baselined finding(s) suppressed"
              % len(accepted))
    print("analyze: %d file-family finding(s), %.1fs (budget %.0fs), "
          "report: %s"
          % (len(new), elapsed, args.budget_seconds,
             os.path.relpath(out_path, REPO)))
    if new:
        print("analyze: %d new finding(s)" % len(new))
        rc = 1
    if args.budget_seconds and elapsed > args.budget_seconds:
        print("analyze: BUDGET EXCEEDED: %.1fs > %.0fs — the analysis "
              "stage must stay inside the verify budget"
              % (elapsed, args.budget_seconds))
        rc = 1
    if rc == 0:
        print("analyze: clean")
    return rc


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # | head closing stdout is not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
