"""nns-san: the concurrency race/deadlock analyzer CLI.

    nns-san --race [paths...]     # AST concurrency lint (default: the
                                  # installed nnstreamer_tpu package)
    nns-san --deadlock "a ! b"    # graph deadlock/capacity findings only
    nns-san --self-check          # diagnostic catalog covers the code?
    nns-san --json --race ...     # machine-readable findings

Exit codes: 0 clean, 1 warnings only, 2 errors (and 1 on --self-check
failure); ``--strict`` treats warnings as errors. The RUNTIME half of the
sanitizer is enabled per run with ``NNS_TPU_SANITIZE=1`` (see
docs/sanitizer.md) — this CLI is the static half.
"""

from __future__ import annotations

import argparse
import json
import sys


def _emit(report, as_json: bool, strict: bool) -> int:
    rc = report.exit_code
    if strict and rc == 1:
        rc = 2
    if as_json:
        print(json.dumps(
            {
                "exit_code": rc,
                "diagnostics": [
                    {
                        "code": d.code,
                        "severity": d.severity.value,
                        "slug": d.slug,
                        "where": d.element,
                        "message": d.message,
                        "hint": d.hint,
                    }
                    for d in report.diagnostics
                ],
            },
            indent=2,
        ))
    else:
        print(report.render())
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nns-san", description=__doc__)
    ap.add_argument(
        "--race", nargs="*", metavar="PATH",
        help="race-lint .py sources (default: the nnstreamer_tpu package)",
    )
    ap.add_argument(
        "--deadlock", metavar="DESC",
        help="graph deadlock/capacity analysis of a pipeline description",
    )
    ap.add_argument(
        "--self-check", action="store_true",
        help="validate the diagnostic catalog against the code",
    )
    ap.add_argument("--json", action="store_true", help="JSON findings")
    ap.add_argument(
        "--strict", action="store_true",
        help="treat warnings as errors (exit 2)",
    )
    args = ap.parse_args(argv)

    if args.self_check:
        from nnstreamer_tpu.analysis.selfcheck import san_self_check

        problems = san_self_check()
        for p in problems:
            print(p)
        if problems:
            print(f"{len(problems)} catalog problem(s)")
            return 1
        print("diagnostic catalog covers the code")
        return 0

    if args.deadlock is not None:
        from nnstreamer_tpu.analysis.diagnostics import LintReport
        from nnstreamer_tpu.analysis.lint import DEADLOCK_CODES, lint

        full = lint(args.deadlock)
        report = LintReport(
            [d for d in full.diagnostics if d.code in DEADLOCK_CODES]
        )
        return _emit(report, args.json, args.strict)

    if args.race is not None:
        import os

        import nnstreamer_tpu
        from nnstreamer_tpu.analysis.racecheck import run_race_lint

        paths = args.race or [os.path.dirname(nnstreamer_tpu.__file__)]
        report = run_race_lint(paths)
        return _emit(report, args.json, args.strict)

    ap.error("one of --race, --deadlock, --self-check is required")
    return 2  # pragma: no cover - ap.error exits


if __name__ == "__main__":
    sys.exit(main())
