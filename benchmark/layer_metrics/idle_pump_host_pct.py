"""Device-idle seconds inside ``nns.llm.pump`` or any span under it (every phase
of the batcher's pump on the host, the token readback and the harvest) over the
traced window: the share of the window the device waited for the pump's host code."""
from benchmark.lib import host_spans


def read(ctx):
    secs = host_spans.idle_overlap(ctx, host_spans.PUMP)
    w = ctx["trace"]["window_s"]
    return None if secs is None or not w else 100.0 * secs / w
