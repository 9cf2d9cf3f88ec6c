"""Device-idle seconds inside ``nns.llm.emit`` (the source node handing a
pump's token frames to the executor, one by one, before the next pump may
start) and outside any pump, over the traced window."""
from benchmark.lib import host_spans


def read(ctx):
    secs = host_spans.idle_overlap(ctx, host_spans.EMIT, minus=host_spans.PUMP)
    w = ctx["trace"]["window_s"]
    return None if secs is None or not w else 100.0 * secs / w
