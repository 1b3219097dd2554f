"""``python -m fedtorch_tpu.lint`` / ``fedtorch-tpu lint`` entry point.

Runs the tracing-hazard analyzer over the default targets (the package
plus ``scripts/`` and ``run_tpu.py``), diffs against the checked-in
baseline, and exits non-zero only on NEW findings — the regression
gate ``scripts/lint_suite.py`` and ``tests/test_lint_suite.py`` wrap.

    python -m fedtorch_tpu.lint                 # gate (default paths)
    python -m fedtorch_tpu.lint --all           # ignore the baseline
    python -m fedtorch_tpu.lint --write-baseline  # accept current state
    python -m fedtorch_tpu.lint --explain       # rule catalog
    python -m fedtorch_tpu.lint path/to/file.py # specific targets

``--concurrency`` runs the host-plane concurrency audit (FTH rules,
``concurrency_audit.py``) instead: the static lock-acquisition graph
and thread-escape map over the package + scripts, gated against
``lint/concurrency_baseline.json`` — except FTH001 lock-order cycles,
which are hard errors and bypass the baseline entirely.

``--audit`` (also reachable as ``fedtorch-tpu audit``) runs the OTHER
halves instead of the AST gate: the registry-drift checker
(``registry_audit``, stdlib-only), the concurrency gate (also
stdlib), and the program-level audit (``program_audit`` — abstractly
lowers every legal round-program builder cell on the active backend
and checks the HLO/jaxpr; needs jax). ``--registry-only`` skips the
lowering half for jax-free lanes; ``--write-baseline`` under
``--audit`` re-pins ``lint/program_baseline.json``; ``--out FILE``
writes the audit report document.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from fedtorch_tpu.lint.analyzer import analyze_paths
from fedtorch_tpu.lint.findings import (
    diff_against_baseline, load_baseline, save_baseline,
)
from fedtorch_tpu.lint.rules import explain

# "tools" is walked when a top-level tools/ dir exists (none today —
# package tools live under fedtorch_tpu/tools, which the package walk
# covers); listing it keeps a future top-level tools/ inside the gate
DEFAULT_TARGETS = ("fedtorch_tpu", "scripts", "tools", "run_tpu.py")
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "baseline.json")


def repo_root() -> str:
    """The directory the package sits in (works from a checkout)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="fedtorch-tpu lint",
        description="TPU tracing-hazard static analysis "
                    "(docs/static_analysis.md)")
    p.add_argument("targets", nargs="*", default=None,
                   help="files/dirs relative to the repo root "
                        f"(default: {' '.join(DEFAULT_TARGETS)})")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detected)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline JSON path")
    p.add_argument("--all", action="store_true",
                   help="report every finding, ignoring the baseline")
    p.add_argument("--write-baseline", action="store_true",
                   help="accept the current findings as the baseline")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--explain", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--audit", action="store_true",
                   help="run the program-level + registry-drift audit "
                        "(FTP/FTC rules) instead of the AST gate")
    p.add_argument("--concurrency", action="store_true",
                   help="run the host-plane concurrency audit (FTH "
                        "rules) instead of the tracing AST gate")
    p.add_argument("--registry-only", action="store_true",
                   help="with --audit: only the stdlib registry-drift "
                        "half (no jax, no program lowering)")
    p.add_argument("--out", default=None,
                   help="with --audit: write the report document "
                        "(JSON) to this path")
    return p


def run_concurrency(args) -> int:
    """The ``fedtorch-tpu lint --concurrency`` gate: FTH findings over
    the package + scripts, diffed against
    ``lint/concurrency_baseline.json``. FTH001 lock-order cycles are
    HARD errors: they bypass the baseline (and ``--write-baseline``
    refuses to pin them)."""
    from fedtorch_tpu.lint.concurrency_audit import (
        CONCURRENCY_BASELINE_REL, CONCURRENCY_TARGETS,
        audit_concurrency_paths, split_hard_findings,
    )

    root = args.root or repo_root()
    targets = args.targets or list(CONCURRENCY_TARGETS)
    baseline_path = args.baseline if args.baseline != DEFAULT_BASELINE \
        else os.path.join(root, CONCURRENCY_BASELINE_REL)
    findings = audit_concurrency_paths(root, targets)
    hard, soft = split_hard_findings(findings)

    if args.write_baseline:
        save_baseline(baseline_path, soft)
        print(f"wrote {len(soft)} finding(s) to {baseline_path}")
        for f in hard:
            print(f.render())
        if hard:
            print(f"fedtorch_tpu.lint --concurrency: {len(hard)} "
                  "FTH001 cycle(s) NOT baselined — hard errors")
            return 1
        return 0

    if args.all:
        new, matched = findings, 0
    else:
        new_soft, matched = diff_against_baseline(
            soft, load_baseline(baseline_path))
        new = sorted(hard + new_soft,
                     key=lambda f: (f.path, f.line, f.rule))

    report = {"total": len(findings), "baselined": matched,
              "hard_errors": len(hard),
              "new": [f.__dict__ for f in new]}
    if args.format == "json":
        print(json.dumps(report, indent=2))
    else:
        for f in new:
            print(f.render())
        label = "finding(s)" if args.all else "NEW finding(s)"
        print(f"fedtorch_tpu.lint --concurrency: {len(new)} {label} "
              f"({len(findings)} total, {matched} baselined, "
              f"{len(hard)} hard)")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"concurrency report written to {args.out}")
    return 1 if new else 0


def run_audit(args) -> int:
    """The ``fedtorch-tpu audit`` gate: registry drift (stdlib) +
    program-level HLO/jaxpr checks over every builder cell."""
    import json as _json

    from fedtorch_tpu.lint.registry_audit import audit_registries

    from fedtorch_tpu.lint.concurrency_audit import concurrency_gate

    root = args.root or repo_root()
    reg_findings = audit_registries(root)
    # the concurrency gate is stdlib like the registry half: FTH001
    # hard errors + soft findings not in concurrency_baseline.json
    conc_new, conc_total = concurrency_gate(root)
    report = {"registry_findings": len(reg_findings),
              "concurrency_findings": len(conc_new),
              "concurrency_total": conc_total}
    findings = list(reg_findings) + conc_new
    if not args.registry_only:
        from fedtorch_tpu.lint.program_audit import (
            PROGRAM_BASELINE, audit_programs,
        )
        baseline = args.baseline if args.baseline != DEFAULT_BASELINE \
            else PROGRAM_BASELINE
        prog_new, prog_report = audit_programs(
            baseline_path=baseline,
            write_baseline=args.write_baseline,
            log=(lambda *_: None) if args.format == "json" else print)
        findings += prog_new
        report.update(prog_report)
    if args.format == "json":
        # stdout stays one parseable document — findings ride inside it
        print(_json.dumps({
            "new": [f.__dict__ for f in findings], **report}, indent=2))
    else:
        for f in findings:
            print(f.render())
        print(f"fedtorch-tpu audit: {len(findings)} NEW finding(s) "
              f"({len(reg_findings)} registry, "
              f"{len(conc_new)} concurrency, "
              f"{len(findings) - len(reg_findings) - len(conc_new)} "
              f"program; wall {report.get('wall_s', 0)}s)")
    if args.out:
        report_doc = dict(report)
        report_doc["findings"] = [f.__dict__ for f in findings]
        with open(args.out, "w") as fh:
            _json.dump(report_doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"audit report written to {args.out}")
    return 1 if findings else 0


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.explain:
        print(explain())
        return 0
    if args.audit:
        return run_audit(args)
    if args.concurrency:
        return run_concurrency(args)
    root = args.root or repo_root()
    targets = args.targets or list(DEFAULT_TARGETS)
    findings = analyze_paths(root, targets)

    if args.write_baseline:
        save_baseline(args.baseline, findings)
        print(f"wrote {len(findings)} finding(s) to {args.baseline}")
        return 0

    if args.all:
        new, matched = findings, 0
    else:
        baseline = load_baseline(args.baseline)
        new, matched = diff_against_baseline(findings, baseline)

    if args.format == "json":
        print(json.dumps({
            "total": len(findings), "baselined": matched,
            "new": [f.__dict__ for f in new]}, indent=2))
    else:
        for f in new:
            print(f.render())
        label = "finding(s)" if args.all else "NEW finding(s)"
        print(f"fedtorch_tpu.lint: {len(new)} {label} "
              f"({len(findings)} total, {matched} baselined)")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
