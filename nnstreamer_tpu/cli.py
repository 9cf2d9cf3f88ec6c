"""nns-launch: run pipeline descriptions from the command line.

The reference's CLI is GStreamer's gst-launch-1.0 / gst-inspect-1.0
(SURVEY.md §1 L6). Usage:

    python -m nnstreamer_tpu.cli "videotestsrc num-frames=10 ! \\
        tensor_converter ! tensor_transform mode=typecast option=float32 ! \\
        tensor_sink name=out"

    python -m nnstreamer_tpu.cli --inspect                # list elements
    python -m nnstreamer_tpu.cli --inspect tensor_filter  # element detail
    python -m nnstreamer_tpu.cli --dot "..." > graph.dot  # graph dump
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _inspect(name: str | None) -> int:
    from nnstreamer_tpu import registry

    if not name:
        print("Available elements:")
        for n in registry.available(registry.KIND_ELEMENT):
            cls = registry.get(registry.KIND_ELEMENT, n)
            doc = (cls.__doc__ or "").strip().splitlines()
            print(f"  {n:24s} {doc[0] if doc else ''}")
        for kind, label in (
            (registry.KIND_FILTER, "filter backends"),
            (registry.KIND_DECODER, "decoder subplugins"),
            (registry.KIND_CONVERTER, "converter subplugins"),
        ):
            names = registry.available(kind)
            if names:
                print(f"\nAvailable {label}: {', '.join(names)}")
        return 0
    cls = registry.get(registry.KIND_ELEMENT, name)
    print(f"Element: {name}\n")
    print(cls.__doc__ or "(no documentation)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nns-launch", description=__doc__)
    ap.add_argument("description", nargs="?", help="pipeline description")
    ap.add_argument("--inspect", nargs="?", const="", default=None, metavar="ELEMENT")
    ap.add_argument("--dot", action="store_true", help="print graphviz, don't run")
    ap.add_argument(
        "--check", action="store_true",
        help="statically lint the pipeline without starting it; "
        "exit 0 clean / 1 warnings / 2 errors (see docs/linting.md)",
    )
    ap.add_argument("--timeout", type=float, default=None, help="run timeout (s)")
    ap.add_argument(
        "--stats", action="store_true",
        help="print per-node stats JSON (enables nns-obs metrics, so the "
        "rows carry latency_p50/p95/p99_ms and queue-wait percentiles)",
    )
    ap.add_argument(
        "--metrics", metavar="FILE", default=None,
        help="write a one-shot nns-obs JSON snapshot at EOS "
        "(docs/observability.md; nns-top renders it)",
    )
    ap.add_argument(
        "--trace", metavar="FILE", default=None,
        help="write chrome://tracing JSON of per-element frame spans",
    )
    ap.add_argument(
        "--profile", metavar="DIR", default=None,
        help="capture an on-device (XLA/TPU) profile into a TensorBoard logdir",
    )
    ap.add_argument("--quiet", "-q", action="store_true")
    from nnstreamer_tpu import __version__

    ap.add_argument(
        "--version", action="version", version=f"nns-launch {__version__}"
    )
    args = ap.parse_args(argv)

    if args.inspect is not None:
        return _inspect(args.inspect or None)
    if not args.description:
        ap.error("pipeline description required")

    if args.check:
        from nnstreamer_tpu.analysis import annotated_dot, lint

        result = lint(args.description)
        if args.dot:
            print(annotated_dot(result))
        elif not args.quiet or result.diagnostics:
            print(result.render())
        return result.exit_code

    from nnstreamer_tpu.elements.base import ElementError, NegotiationError
    from nnstreamer_tpu.pipeline.parse import ParseError, parse_pipeline

    # gst-launch-style diagnostics: construction/negotiation failures are
    # user errors — one clean line and rc 1, never a traceback dump
    try:
        pipeline = parse_pipeline(args.description)
        pipeline.negotiate()
    except (ParseError, NegotiationError, ElementError, KeyError, ValueError) as exc:
        print(f"nns-launch: {exc}", file=sys.stderr)
        return 1
    if args.dot:
        print(pipeline.dump_dot())
        return 0
    if not args.quiet:
        print(f"Setting pipeline PLAYING ({len(pipeline.elements)} elements)", file=sys.stderr)
    import contextlib

    from nnstreamer_tpu import trace as trace_mod
    from nnstreamer_tpu.obs import metrics as obs_metrics

    if args.stats or args.metrics:
        # percentile columns need the histograms recording; executors
        # resolve the registry at construction, which happens in run()
        obs_metrics.enable()
    tracer = trace_mod.enable() if args.trace else None
    profile_cm = (
        trace_mod.device_profile(args.profile) if args.profile
        else contextlib.nullcontext()
    )
    t0 = time.perf_counter()
    timed_out = False
    with profile_cm:
        try:
            ex = pipeline.run(timeout=args.timeout)
        except TimeoutError:
            # operator-requested bound on an endless pipeline: a stop, not a bug
            ex = pipeline._executor
            timed_out = True
        except (ElementError, NegotiationError, RuntimeError) as exc:
            print(f"nns-launch: pipeline error: {exc}", file=sys.stderr)
            return 1
    dt = time.perf_counter() - t0
    if tracer is not None:
        tracer.save(args.trace)
        if not args.quiet:
            print(f"Trace written to {args.trace}", file=sys.stderr)
    if not args.quiet:
        msg = "Timeout reached" if timed_out else "EOS"
        print(f"{msg} after {dt:.3f}s", file=sys.stderr)
        for e in pipeline.elements:
            if hasattr(e, "rendered"):
                print(f"  {e.name}: rendered {e.rendered} frames", file=sys.stderr)
    if args.metrics:
        from nnstreamer_tpu.obs import expo

        expo.dump_json(
            args.metrics,
            expo.snapshot(obs_metrics.get(), ex.stats(), ex.totals()),
        )
        if not args.quiet:
            print(f"Metrics snapshot written to {args.metrics}", file=sys.stderr)
    if args.stats:
        stats = ex.stats()
        # pipeline-wide frame accounting rides alongside the per-node
        # rows (produced / rendered / dropped-by-reason / balance);
        # element names are user-chosen, so never clobber a node row
        totals_key = "__pipeline__"
        while totals_key in stats:
            totals_key = "_" + totals_key
        stats[totals_key] = ex.totals()
        print(json.dumps(stats, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
