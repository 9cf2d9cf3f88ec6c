"""95th percentile of (actual push - due) over the open-loop generator's
pushes: a starved generator must not read as a fast server."""
from benchmark.lib.stats import percentile


def read(ctx):
    xs = ctx.get("gen_late_ms")
    return percentile(xs, 95) if xs else None
