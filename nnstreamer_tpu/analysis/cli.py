"""nns-lint: the standalone static-analyzer CLI.

    nns-lint "videotestsrc ! tensor_converter ! tensor_sink"
    nns-lint --dot "..." > graph.dot     # diagnostics painted on nodes
    nns-lint --json "..."                # machine-readable findings
    nns-lint --self-check                # PROPERTIES schemas cover code?
    nns-lint --strict "..."              # warnings fail hard (exit 2)

Exit codes: 0 clean, 1 warnings only, 2 errors (and 1 on --self-check
failure). The pipeline is parsed and analyzed but NEVER started. The
sibling `nns-san` CLI covers the concurrency race lint and the runtime
sanitizer (docs/sanitizer.md).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nns-lint", description=__doc__)
    ap.add_argument("description", nargs="?", help="pipeline description")
    ap.add_argument(
        "--dot", action="store_true",
        help="print graphviz with diagnostics annotated on the nodes",
    )
    ap.add_argument("--json", action="store_true", help="JSON findings")
    ap.add_argument(
        "--self-check", action="store_true",
        help="verify every builtin element's PROPERTIES schema covers the "
        "properties its code reads",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="treat warnings as errors (warnings-only runs exit 2)",
    )
    ap.add_argument("--quiet", "-q", action="store_true")
    args = ap.parse_args(argv)

    if args.self_check:
        from nnstreamer_tpu.analysis.selfcheck import main as selfcheck_main

        return selfcheck_main()
    if not args.description:
        ap.error("pipeline description required (or --self-check)")

    from nnstreamer_tpu.analysis import annotated_dot, lint

    result = lint(args.description)
    rc = result.exit_code
    if args.strict and rc == 1:
        rc = 2  # warnings fail hard under --strict
    if args.dot:
        print(annotated_dot(result))
        return rc
    if args.json:
        print(json.dumps(
            {
                "exit_code": rc,
                "diagnostics": [
                    {
                        "code": d.code,
                        "severity": d.severity.value,
                        "slug": d.slug,
                        "element": d.element,
                        "message": d.message,
                        "hint": d.hint,
                    }
                    for d in result.diagnostics
                ],
            },
            indent=2,
        ))
        return rc
    if not args.quiet or result.diagnostics:
        print(result.render())
    return rc


if __name__ == "__main__":
    sys.exit(main())
