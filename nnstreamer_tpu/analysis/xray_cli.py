"""nns-xray: the whole-chain compile-unit analyzer CLI.

    nns-xray "videotestsrc device=true ! tensor_converter ! ..."
    nns-xray --json "..."          # machine-readable chains + findings
    nns-xray --dispatch            # which Pallas/jnp kernels engage
    nns-xray --self-check          # W120-W124 emitters<->catalog<->docs
    nns-xray --strict "..."        # warnings fail hard (exit 2)

Reports compile units (chains of fused segments joined by device
handoffs), per-chain params/activation/transient bytes, predicted
per-frame host-transfer bytes at every boundary, and the jaxpr lint
findings (NNS-W120..W124) — see docs/chain-analysis.md. Exit codes:
0 clean/degraded, 1 warnings only, 2 errors. The pipeline is compiled
(negotiation runs, backends open) but NEVER started.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nns-xray", description=__doc__)
    ap.add_argument("description", nargs="?", help="pipeline description")
    ap.add_argument("--json", action="store_true", help="JSON report")
    ap.add_argument(
        "--dispatch", action="store_true",
        help="print the kernel dispatch table (impl=auto: pallas vs "
        "fallback, statically and measured by tiny probe invocations)",
    )
    ap.add_argument(
        "--no-probe", action="store_true",
        help="with --dispatch: static columns only, no probe invocations",
    )
    ap.add_argument(
        "--self-check", action="store_true",
        help="verify the W120-W124 emitters<->catalog<->docs wiring",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="treat warnings as errors (warnings-only runs exit 2)",
    )
    ap.add_argument("--quiet", "-q", action="store_true")
    args = ap.parse_args(argv)

    if args.self_check:
        from nnstreamer_tpu.analysis.selfcheck import xray_self_check

        problems = xray_self_check()
        for p in problems:
            print(p)
        print(
            "xray self-check: "
            + ("OK" if not problems else f"{len(problems)} problem(s)")
        )
        return 1 if problems else 0

    from nnstreamer_tpu.analysis.xray import dispatch_table, xray

    if args.dispatch and not args.description:
        rows = dispatch_table(run=not args.no_probe)
        if args.json:
            print(json.dumps(rows, indent=2))
        else:
            for row in rows:
                measured = ",".join(row["measured"]) or "-"
                line = (
                    f"{row['op']}: on-tpu={row['auto_on_tpu']} "
                    f"here={row['auto_here']} measured={measured}"
                )
                if row.get("error"):
                    line += f" ({row['error']})"
                print(line)
        return 0
    if not args.description:
        ap.error(
            "pipeline description required (or --dispatch / --self-check)"
        )

    result = xray(args.description)
    if args.dispatch:
        result.dispatch = dispatch_table(run=not args.no_probe)
    rc = result.exit_code
    if args.strict and rc == 1:
        rc = 2  # warnings fail hard under --strict
    if args.json:
        print(json.dumps(
            {
                "exit_code": rc,
                "degraded": result.degraded,
                "chains": [
                    {
                        "name": c.name,
                        "segments": c.segments,
                        "n_ops": c.n_ops,
                        "params_bytes": c.cost.params_bytes,
                        "activation_bytes": c.cost.activation_bytes,
                        "transient_bytes": c.cost.transient_bytes,
                        "boundary_in_bytes": c.cost.boundary_in_bytes,
                        "boundary_out_bytes": c.cost.boundary_out_bytes,
                        "notes": c.notes,
                    }
                    for c in result.chains
                ],
                "boundaries": [
                    {
                        "producer": b.producer,
                        "consumer": b.consumer,
                        "direction": b.direction,
                        "bytes_per_frame": b.bytes_per_frame,
                        "reason": b.reason,
                    }
                    for b in result.boundaries
                ],
                "predicted": result.predicted,
                "predicted_tpu": result.predicted_tpu,
                "dispatch": result.dispatch,
                "notes": result.notes,
                "errors": result.errors,
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
    if not args.quiet or result.diagnostics or result.errors:
        print(result.render())
    return rc


if __name__ == "__main__":
    sys.exit(main())
