"""nns-kscope: the static Pallas kernel analyzer CLI.

    nns-kscope                     # VMEM/alignment/roofline per kernel x shape
    nns-kscope --json              # machine-readable rows + findings
    nns-kscope --kernel flash_attention
    nns-kscope --self-check        # wiring check + parity sweep vs the jnp refs
    nns-kscope --self-check --full # ... over the full shape grid (slow)
    nns-kscope --engage            # prove requested pallas paths engage
    nns-kscope --strict            # warnings fail hard (exit 2)

Reports, for every registered kernel x representative shape
(ops/pallas/registry.py): per-grid-step VMEM residency vs the
``[tpu] vmem_bytes`` bound, lane/sublane tile alignment, index-map
hazards, and a roofline cost row (HBM bytes by index-map transition
counting, FLOPs, arithmetic intensity) — all statically, no device.
Findings are NNS-W127/W128 (docs/kernel-analysis.md). ``--engage``
runs each kernel's tiny interpret-mode probe and diffs the dispatch
tally; a requested pallas path that silently fell back exits nonzero.
Exit codes: 0 clean, 1 warnings only, 2 errors.
"""

from __future__ import annotations

import argparse
import json
import sys


def _print_case(r) -> None:
    flags = []
    if r.over_budget:
        flags.append("OVER-VMEM")
    if r.misaligned:
        flags.append("MISALIGNED:" + ",".join(b.name for b in r.misaligned))
    if r.hazards:
        flags.append(f"{len(r.hazards)} hazard(s)")
    tail = (" [" + " ".join(flags) + "]") if flags else ""
    print(
        f"{r.kernel}:{r.case}: grid={r.grid} "
        f"vmem={r.vmem_bytes}/{r.vmem_bound}B "
        f"hbm={r.cost.hbm_bytes}B flops={r.cost.flops} "
        f"ai={r.cost.arithmetic_intensity:.2f}{tail}"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nns-kscope", description=__doc__)
    ap.add_argument("--json", action="store_true", help="JSON report")
    ap.add_argument(
        "--kernel", default="",
        help="analyze only this registered kernel",
    )
    ap.add_argument(
        "--self-check", action="store_true",
        help="W127-W129 emitters<->catalog<->docs + registry wiring, "
        "then the differential sweep (interpreter off-TPU, compiled "
        "kernels on a chip) vs each kernel's "
        "jnp reference (tier-1 shape subset)",
    )
    ap.add_argument(
        "--full", action="store_true",
        help="with --self-check: sweep the FULL shape grid (slow)",
    )
    ap.add_argument(
        "--engage", action="store_true",
        help="run each kernel's tiny probe with pallas requested and "
        "diff the dispatch tally; nonzero if any path fell back",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="treat warnings as errors (warnings-only runs exit 2)",
    )
    ap.add_argument("--quiet", "-q", action="store_true")
    args = ap.parse_args(argv)

    from nnstreamer_tpu.analysis import kernels as K
    from nnstreamer_tpu.ops.pallas import registry as kreg

    specs = None
    if args.kernel:
        spec = kreg.find(args.kernel)
        if spec is None:
            print(
                f"unknown kernel {args.kernel!r}; registered: "
                + ", ".join(kreg.names()),
                file=sys.stderr,
            )
            return 2
        specs = [spec]

    if args.self_check:
        from nnstreamer_tpu.analysis.selfcheck import kscope_self_check

        problems = kscope_self_check()
        for p in problems:
            print(p)
        rows = K.differential_sweep(specs, full=args.full)
        for row in rows:
            if row["ok"]:
                if not args.quiet:
                    print(
                        f"{row['kernel']}:{row['case']}: OK "
                        f"(max_err={row['max_err']:.2e})"
                    )
            else:
                print(
                    f"{row['kernel']}:{row['case']}: FAIL {row['error']}"
                )
        bad = [r for r in rows if not r["ok"]]
        print(
            "kscope self-check: "
            + ("OK" if not problems and not bad
               else f"{len(problems)} problem(s), {len(bad)} parity "
               "failure(s)")
        )
        return 1 if problems or bad else 0

    if args.engage:
        rows = K.engage(specs)
        if args.json:
            print(json.dumps(rows, indent=2))
        else:
            for row in rows:
                impls = ",".join(row["impls"]) or "-"
                line = (
                    f"{row['kernel']} ({row['op']}): "
                    f"{'engaged' if row['ok'] else 'FELL BACK'} "
                    f"[{impls}]"
                )
                if row.get("error"):
                    line += f" ({row['error']})"
                print(line)
        return 0 if all(r["ok"] for r in rows) else 1

    reports, lint_report = K.analyze(specs)
    rc = lint_report.exit_code
    if args.strict and rc == 1:
        rc = 2  # warnings fail hard under --strict
    if args.json:
        print(json.dumps(
            {
                "exit_code": rc,
                "cases": [r.to_row() for r in reports],
                "diagnostics": [
                    {
                        "code": d.code,
                        "severity": d.severity.value,
                        "slug": d.slug,
                        "element": d.element,
                        "message": d.message,
                        "hint": d.hint,
                    }
                    for d in lint_report.diagnostics
                ],
            },
            indent=2,
        ))
        return rc
    if not args.quiet:
        for r in reports:
            _print_case(r)
    if lint_report.diagnostics:
        print(lint_report.render())
    elif not args.quiet:
        print(f"{len(reports)} kernel case(s) clean")
    return rc


if __name__ == "__main__":
    sys.exit(main())
