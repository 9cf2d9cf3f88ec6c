"""Seconds of the traced window under the program's ``nns.host.stall``
intervals of kind ``machine`` (the machine stopped the process: nothing of it
ran), clipped to the window, / window. None where the window holds no
``nns.host.watch``: no sentinel ran."""
from benchmark.lib import host_spans, host_stalls


def read(ctx):
    if not host_stalls.watched(ctx):
        return None
    window = [tuple(ctx["trace"]["window_ns"])]
    stalls = host_stalls.stall_intervals(ctx)
    host_spans.log(f"host_stall_pct: {len(stalls)} machine stalls in the window")
    return 100.0 * host_spans.overlap_seconds(window, stalls) / ctx["trace"]["window_s"]
