"""Analysis engine: every pass family over one shared project parse.

``run_all`` is what ``scripts/analyze_all.py`` / ``scripts/opslint.py``
(``make analyze``) drive: the syntactic opslint passes (OPS1xx–5xx),
the package-wide metrics inventory (OPS4xx), and the interprocedural
dataflow families (OPS6xx buffer ownership, OPS7xx mesh consistency,
OPS8xx blocking transfers, OPS9xx lockset/atomicity) all run over ONE
:class:`dataflow.Project` parse, share the suppression-comment +
baseline machinery, and feed the OPS001 stale-suppression audit — a
pragma, baseline fingerprint, or guard-spec entry that silences or
checks nothing is itself a finding, so the suppression surface can
only shrink.

Determinism contract (tested): two runs over an unchanged tree produce
byte-identical findings — everything is sorted, nothing depends on dict
iteration order, filesystem walk order is normalized by
``dataflow._iter_py``.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import dataflow, ops6xx, ops7xx, ops8xx, ops9xx, ops10xx, opslint
from .opslint import Finding

# the complete rule catalog across every family (docs/static-analysis.md)
ALL_RULES: Dict[str, Tuple[str, str]] = {}
ALL_RULES.update(opslint.RULES)
ALL_RULES.update(ops6xx.RULES)
ALL_RULES.update(ops7xx.RULES)
ALL_RULES.update(ops8xx.RULES)
ALL_RULES.update(ops9xx.RULES)
ALL_RULES.update(ops10xx.RULES)

# rule id -> family label for the machine-readable report
def family_of(rule: str) -> str:
    if rule in ops6xx.RULES or rule in ops7xx.RULES \
            or rule in ops8xx.RULES or rule in ops9xx.RULES \
            or rule in ops10xx.RULES:
        return "dataflow"
    return "opslint"


def dataflow_passes() -> List[dataflow.DataflowPass]:
    return (ops6xx.make_passes() + ops7xx.make_passes()
            + ops8xx.make_passes() + ops9xx.make_passes()
            + ops10xx.make_passes())


def run_all(paths: Sequence[str], root: Optional[str] = None,
            axis_paths: Sequence[str] = (),
            rules: Optional[Iterable[str]] = None,
            report_paths: Optional[Set[str]] = None) -> List[Finding]:
    """All families over ``paths``; suppression pragmas applied; stale
    pragmas reported as OPS001. Baseline handling is the caller's
    (CLI) job — fingerprints of the returned findings feed it.

    ``report_paths`` (incremental mode): parse and summarize the whole
    scope but REPORT only for those repo-relative files. The contract —
    asserted in-suite — is that the result equals a whole-tree run's
    findings restricted to those files."""
    project = dataflow.Project(paths, root=root, axis_paths=axis_paths)

    def in_report(path: str) -> bool:
        return report_paths is None or path in report_paths

    raw: List[Finding] = []
    inv = opslint._MetricsInventory()
    for mod in project.modules:
        # metrics families resolve package-wide: collect from EVERY
        # module even in incremental mode, report per-file below
        opslint._METRICS_PASS.collect(mod.path, mod.tree, inv)
        if not in_report(mod.path):
            continue
        for p in opslint._AST_PASSES:
            raw.extend(p.run(mod.path, mod.tree, mod.source))
    raw.extend(f for f in opslint._METRICS_PASS.finish(inv)
               if in_report(f.path))
    raw.extend(dataflow.Analyzer(project, dataflow_passes(),
                                 report_paths=report_paths).run())

    # -- suppression + OPS001 stale-pragma audit ------------------------
    kept: List[Finding] = []
    suppressed: List[Finding] = []
    by_file: Dict[str, List[Finding]] = {}
    for f in raw:
        by_file.setdefault(f.path, []).append(f)
    mod_by_path = {m.path: m for m in project.modules
                   if in_report(m.path)}
    for path in sorted(mod_by_path):
        mod = mod_by_path[path]
        smap = opslint._suppressed_lines(mod.source)
        for f in by_file.get(path, []):
            if f.rule in smap.get(f.line, ()):
                suppressed.append(f)
            else:
                kept.append(f)
        # a pragma that silenced nothing is stale (OPS001) — unless it
        # names OPS001 itself (escape hatch for intentional keeps)
        hit_lines = {(g.line, g.rule) for g in suppressed
                     if g.path == path}
        for line, rule_ids in opslint.suppression_sites(mod.source):
            for rid in sorted(rule_ids):
                if rid == "OPS001":
                    continue
                if (line, rid) in hit_lines or (line + 1, rid) in hit_lines:
                    continue
                kept.append(Finding(
                    "OPS001", path, line,
                    "suppression comment disables %s but no %s finding "
                    "exists on this line anymore — delete the pragma"
                    % (rid, rid),
                    symbol="stale.%s.L%d" % (rid, line)))
    # findings in files outside the parsed module set (shouldn't happen)
    seen_paths = set(mod_by_path)
    kept.extend(f for f in raw
                if f.path not in seen_paths and f not in kept)

    if rules is not None:
        want = set(rules)
        kept = [f for f in kept if f.rule in want]
    uniq: Dict[Tuple[str, str, int, str, str], Finding] = {}
    for f in kept:
        uniq.setdefault((f.path, f.line, f.rule, f.symbol, f.message), f)
    return sorted(uniq.values(),
                  key=lambda f: (f.path, f.line, f.rule, f.symbol,
                                 f.message))


# repo root (engine.py lives at paddle_operator_tpu/analysis/engine.py)
REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def default_paths() -> List[str]:
    """The analysis scope both CLIs share: the package and the
    operational scripts."""
    return [os.path.join(REPO_ROOT, "paddle_operator_tpu"),
            os.path.join(REPO_ROOT, "scripts")]


def axis_paths() -> List[str]:
    """Mesh-axis-vocabulary-only paths (parsed, never linted)."""
    return [os.path.join(REPO_ROOT, "tests"),
            os.path.join(REPO_ROOT, "examples")]


def _entry_file(desc: str) -> str:
    """The repo-relative file a rendered baseline entry points at
    (``Finding.render`` format: ``path:line: RULE [...] msg``)."""
    return desc.split(":", 1)[0]


def _in_scope(entry_file: str, scope: Sequence[str],
              root: Optional[str]) -> bool:
    for p in scope:
        rel = os.path.relpath(p, root) if root else p
        rel = rel.rstrip("/")
        if rel in (".", ""):
            return True
        if entry_file == rel or entry_file.startswith(rel + "/") \
                or entry_file.startswith(rel + os.sep):
            return True
    return False


def stale_baseline_findings(findings: Sequence[Finding],
                            baseline: Dict[str, str],
                            baseline_path: str,
                            scope: Sequence[str] = (),
                            root: Optional[str] = None,
                            rules: Optional[Iterable[str]] = None
                            ) -> List[Finding]:
    """OPS001 for baseline fingerprints matching no current finding —
    the committed baseline can only shrink; ``--prune-baseline``
    rewrites it.

    Staleness is only judged for entries whose file lies INSIDE the
    analyzed ``scope`` (a partial-path run has no opinion about the rest
    of the tree), and never when a ``--rules`` subset is active (a rule
    the run did not execute cannot have gone stale)."""
    if rules is not None:
        return []
    live = {f.fingerprint() for f in findings}
    out = []
    for fp in sorted(set(baseline) - live):
        if scope and not _in_scope(_entry_file(baseline[fp]), scope, root):
            continue
        out.append(Finding(
            "OPS001", os.path.basename(baseline_path), 0,
            "baseline entry %s (%s) matches no current finding — run "
            "--prune-baseline to drop it" % (fp, baseline[fp]),
            symbol="stale.baseline.%s" % fp))
    return out


def prune_baseline(findings: Sequence[Finding], baseline_path: str,
                   scope: Sequence[str] = (),
                   root: Optional[str] = None) -> Tuple[int, int]:
    """Rewrite the baseline keeping entries a live finding still matches
    — plus entries OUTSIDE the analyzed scope, which this run cannot
    judge. Returns (kept, total_before)."""
    old = opslint.load_baseline(baseline_path)
    live = {f.fingerprint() for f in findings}
    keep = {fp: desc for fp, desc in old.items()
            if fp in live
            or (scope and not _in_scope(_entry_file(desc), scope, root))}
    data = {
        "comment": "accepted pre-existing opslint findings; regenerate "
                   "with scripts/opslint.py --update-baseline",
        "findings": dict(sorted(keep.items())),
    }
    import json

    with open(baseline_path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return len(keep), len(old)
