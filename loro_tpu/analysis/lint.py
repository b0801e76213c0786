"""tpulint engine + CLI: ``python -m loro_tpu.analysis.lint [paths...]``.

Runs the rule catalogue (``rules.py``) over the given files/dirs,
applies per-line pragmas and the baseline, and exits non-zero on any
active finding.  Pure stdlib — no jax import — so it runs in
milliseconds as a pre-commit hook or the tier-1 gate test.

    python -m loro_tpu.analysis.lint loro_tpu chip_smoke.py
    python -m loro_tpu.analysis.lint --format=json loro_tpu
    python -m loro_tpu.analysis.lint --write-baseline loro_tpu chip_smoke.py

Every active finding feeds the obs registry
(``analysis.findings_total{rule=...}`` / ``analysis.suppressed_total``)
so lint health rides the same metrics sidecar as everything else.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Sequence

from .core import (
    Finding,
    LintResult,
    ModuleSource,
    all_rules,
    baseline_payload,
    load_baseline,
    parse_pragmas,
)

# repo root = parent of the loro_tpu package: scope predicates match
# repo-relative posix paths ("loro_tpu/sync/server.py", "chip_smoke.py")
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


def _relpath(path: str) -> str:
    """Repo-relative posix path for scope matching.  Files outside the
    repo root re-anchor at their last ``loro_tpu`` component (or a
    ``chip_smoke.py`` basename) so linting a DIFFERENT checkout of this
    project still applies every rule — a silent all-scopes-miss
    "clean" on a foreign tree would be worse than any finding."""
    ap = os.path.abspath(path)
    try:
        rel = os.path.relpath(ap, _REPO_ROOT)
    except ValueError:  # different drive (windows)
        rel = path
    if rel.startswith(".."):
        parts = ap.replace(os.sep, "/").split("/")
        if "loro_tpu" in parts:
            last = len(parts) - 1 - parts[::-1].index("loro_tpu")
            return "/".join(parts[last:])
        if parts[-1] == "chip_smoke.py":
            return "chip_smoke.py"
        rel = path
    return rel.replace(os.sep, "/")


def iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".py"):
            out.append(p)
    return out


def lint_source(source: str, path: str = "loro_tpu/_memory.py",
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint one in-memory module (fixture tests).  ``path`` selects the
    rule scopes that apply.  Returns ALL findings, suppressed ones
    flagged — no baseline."""
    mod = ModuleSource(path, source)
    supp, bad_pragmas = parse_pragmas(mod)
    findings: List[Finding] = list(bad_pragmas)
    for rule in all_rules():
        if rules is not None and rule.id not in rules:
            continue
        if not rule.scope(mod.path):
            continue
        for f in rule.check(mod):
            reason = supp.get(f.line, {}).get(f.rule)
            if reason is not None:
                f.suppressed = True
                f.reason = reason
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_paths(paths: Sequence[str], rules: Optional[Sequence[str]] = None,
               baseline_path: Optional[str] = None) -> LintResult:
    """Lint files/dirs.  ``baseline_path=None`` uses the checked-in
    default when present; pass "" to disable the baseline."""
    if baseline_path is None:
        baseline_path = DEFAULT_BASELINE
    baseline = load_baseline(baseline_path) if baseline_path else {}
    budget = dict(baseline)
    findings: List[Finding] = []
    files = 0
    for fp in iter_py_files(paths):
        with open(fp, "r", encoding="utf-8") as f:
            src = f.read()
        files += 1
        for fnd in lint_source(src, path=_relpath(fp), rules=rules):
            if not fnd.suppressed and budget.get(fnd.key(), 0) > 0:
                budget[fnd.key()] -= 1
                fnd.baselined = True
            findings.append(fnd)
    res = LintResult(findings=findings, files=files)
    _feed_obs(res)
    return res


def _feed_obs(res: LintResult) -> None:
    try:
        from ..obs import metrics as obs

        for rule, n in res.counts().items():
            obs.counter(
                "analysis.findings_total",
                "active tpulint findings by rule",
            ).inc(n, rule=rule)
        for f in res.suppressed:
            obs.counter(
                "analysis.suppressed_total",
                "pragma-suppressed tpulint findings by rule",
            ).inc(rule=f.rule)
        for f in res.baselined:
            obs.counter(
                "analysis.baselined_total",
                "baseline-tolerated tpulint findings by rule",
            ).inc(rule=f.rule)
    except Exception:  # tpulint: disable=LT-EXC(lint must work without the obs package, e.g. vendored)
        pass


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m loro_tpu.analysis.lint",
        description="project-invariant static analysis (docs/ANALYSIS.md)",
    )
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to lint (default: loro_tpu chip_smoke.py)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids (default: all)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: the checked-in "
                         "analysis/baseline.json; pass '' to disable)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current active findings as the baseline")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in all_rules():
            print(f"{r.id:10s} {r.name}: {r.summary}")
        return 0

    paths = args.paths or [
        os.path.join(_REPO_ROOT, "loro_tpu"),
        os.path.join(_REPO_ROOT, "chip_smoke.py"),
    ]
    rules = args.rules.split(",") if args.rules else None
    res = lint_paths(paths, rules=rules, baseline_path=args.baseline)

    if args.write_baseline:
        out = args.baseline or DEFAULT_BASELINE
        with open(out, "w") as f:
            json.dump(baseline_payload(res.active), f, indent=1)
            f.write("\n")
        print(f"baseline: {len(res.active)} finding(s) -> {out}")
        return 0

    if args.format == "json":
        print(json.dumps(res.to_json(), indent=1))
    else:
        for f in res.findings:
            if not f.suppressed:
                print(f.render())
        counts = res.counts()
        summary = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
        print(
            f"tpulint: {len(res.active)} active finding(s) in {res.files} "
            f"file(s) ({summary or 'clean'}); "
            f"{len(res.suppressed)} suppressed, {len(res.baselined)} baselined"
        )
    return 1 if res.active else 0


if __name__ == "__main__":
    sys.exit(main())
