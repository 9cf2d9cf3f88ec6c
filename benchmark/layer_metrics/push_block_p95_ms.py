"""95th percentile of the time ``AppSrc.push`` took to return: back-pressure
from the serversink (a full batch pumps inside ``submit``) through the
executor's queues."""
from benchmark.lib.stats import percentile


def read(ctx):
    xs = ctx.get("push_block_ms")
    return percentile(xs, 95) if xs else None
