"""CLI: ``python -m repro.analysis [--lint] [--contracts] [--protocol]``.

With no mode flags, runs all three layers.  Exits 1 on any violation —
this command IS the CI ``static-analysis`` gate.

    python -m repro.analysis                       # lint+contracts+protocol
    python -m repro.analysis --lint src/ tests/    # lint only, these paths
"""
from __future__ import annotations

import argparse
import sys

DEFAULT_LINT_PATHS = ("src", "tests", "benchmarks")


def _run_lint(paths) -> int:
    from repro.analysis.lint import lint_paths

    violations = lint_paths(paths)
    for v in violations:
        print(f"LINT  {v}")
    print(f"lint: {len(violations)} violation(s) over {list(paths)}")
    return len(violations)


def _run_contracts() -> int:
    # fixtures imports the hot modules (jax included) — lazy by design
    from repro.analysis.fixtures import run_all

    failures = 0
    for report in run_all():
        status = "ok" if report.ok else "FAIL"
        print(f"CONTRACT  {report.contract.name}: {status}")
        for violation in report.violations:
            failures += 1
            print(f"  - {violation}")
    return failures


def _run_protocol() -> int:
    from repro.analysis.protocol import check_scheduler_source

    violations = check_scheduler_source()
    print(f"protocol: scheduler call-order check — "
          f"{'ok' if not violations else f'{len(violations)} violation(s)'}")
    for v in violations:
        print(f"PROTOCOL  {v}")
    return len(violations)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static-analysis gate: kernel contracts, epoch "
                    "protocol, repo lint")
    parser.add_argument("--lint", action="store_true")
    parser.add_argument("--contracts", action="store_true")
    parser.add_argument("--protocol", action="store_true")
    parser.add_argument("paths", nargs="*",
                        help=f"lint roots (default: "
                             f"{' '.join(DEFAULT_LINT_PATHS)})")
    args = parser.parse_args(argv)

    if not (args.lint or args.contracts or args.protocol):
        args.lint = args.contracts = args.protocol = True

    failures = 0
    if args.lint:
        failures += _run_lint(args.paths or list(DEFAULT_LINT_PATHS))
    if args.contracts:
        failures += _run_contracts()
    if args.protocol:
        failures += _run_protocol()

    if failures:
        print(f"\nFAILED: {failures} violation(s)")
        return 1
    print("\nall static-analysis checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
