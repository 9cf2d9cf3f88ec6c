"""95th percentile, over every request due in the window, of (first token
frame at the sink - time due). Tokens come in groups of one pump, so this
tail moves in steps of a pump: it is recorded here, not bounded, and the
tail a user feels is inside ``e2e_p95_ms``."""
from benchmark.lib.stats import percentile


def read(ctx):
    xs = ctx.get("ttft_ms")
    return percentile(xs, 95) if xs else None
