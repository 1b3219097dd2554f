#!/usr/bin/env python
"""One lint gate: ruff (generic style) + fedtorch_tpu.lint (TPU
tracing hazards vs the checked-in baseline) + the host-plane
concurrency audit (FTH rules vs lint/concurrency_baseline.json,
FTH001 cycles unbaselineable — lint/concurrency_audit.py) + the
registry-drift checker (FTC rules: metrics catalog, event names,
fault seams, config<->CLI surface, builder-cell matrix, lint-rule
docs tables — lint/registry_audit.py).

Exit status is non-zero when any half reports NEW findings, so CI
and the tier-1 wrapper (tests/test_lint_suite.py) enforce all with a
single entry point:

    python scripts/lint_suite.py            # the gate
    python scripts/lint_suite.py --explain  # rule catalog

ruff is config-gated: the container this repo grows in does not ship
it, so when the executable is absent the generic half is SKIPPED with
a notice (the pyproject [tool.ruff] config is still the contract any
ruff-equipped environment enforces).  The custom analyzer and the
registry checker are stdlib-only and always run; the program-level
HLO audit (which needs jax) lives behind `fedtorch-tpu audit` and
its own tier-1 tests instead (docs/static_analysis.md).
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUFF_TARGETS = ("fedtorch_tpu", "scripts", "tests", "run_tpu.py")


def run_ruff() -> int | None:
    """ruff check over the configured targets; None = unavailable."""
    exe = shutil.which("ruff")
    if exe is None:
        return None
    proc = subprocess.run([exe, "check", *RUFF_TARGETS], cwd=REPO)
    return proc.returncode


def run_tracing_lint(argv=None) -> int:
    sys.path.insert(0, REPO)
    from fedtorch_tpu.lint.cli import main as lint_main
    return lint_main(argv or [])


def run_concurrency_audit() -> int:
    """The FTH host-plane concurrency half (stdlib-only): FTH001
    hard errors + soft findings not in concurrency_baseline.json."""
    sys.path.insert(0, REPO)
    from fedtorch_tpu.lint.concurrency_audit import concurrency_gate
    new, total = concurrency_gate(REPO)
    for f in new:
        print(f.render())
    return 1 if new else 0


def run_registry_audit() -> int:
    """The FTC registry-drift half (stdlib-only, no baseline: drift
    is fixed at the registry or the emit site, never accepted)."""
    sys.path.insert(0, REPO)
    from fedtorch_tpu.lint.registry_audit import audit_registries
    findings = audit_registries(REPO)
    for f in findings:
        print(f.render())
    return 1 if findings else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--explain":
        return run_tracing_lint(["--explain"])

    failed = False
    ruff_rc = run_ruff()
    if ruff_rc is None:
        print("lint_suite: ruff not installed — generic style half "
              "SKIPPED (pyproject [tool.ruff] is the contract; "
              "install ruff to enforce it)")
    elif ruff_rc != 0:
        print(f"lint_suite: ruff FAILED (rc={ruff_rc})")
        failed = True
    else:
        print("lint_suite: ruff clean")

    lint_rc = run_tracing_lint(argv)
    if lint_rc != 0:
        print("lint_suite: fedtorch_tpu.lint found NEW tracing "
              "hazards (fix them, suppress with a justified "
              "`# lint: disable=...`, or --write-baseline if accepted "
              "— docs/static_analysis.md)")
        failed = True
    else:
        print("lint_suite: fedtorch_tpu.lint clean vs baseline")

    fth_rc = run_concurrency_audit()
    if fth_rc != 0:
        print("lint_suite: host-plane concurrency hazards (FTH) — "
              "fix them, suppress with a justified "
              "`# lint: disable=FTHxxx — why`, or (non-FTH001 only) "
              "accept with `python -m fedtorch_tpu.lint --concurrency "
              "--write-baseline` (docs/static_analysis.md "
              "'The concurrency audit')")
        failed = True
    else:
        print("lint_suite: concurrency audit clean (FTH)")

    ftc_rc = run_registry_audit()
    if ftc_rc != 0:
        print("lint_suite: registry drift (FTC) — fix the catalog, "
              "emit site, docs table or drill it names "
              "(docs/static_analysis.md 'The registry audit')")
        failed = True
    else:
        print("lint_suite: registries in lockstep (FTC clean)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
