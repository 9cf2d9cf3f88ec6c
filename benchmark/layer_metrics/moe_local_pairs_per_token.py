"""(token, expert) pairs that fell on the experts held here, per token and
expert layer, over the decode steps of the traced window (the
``nns.moe.routing`` instants). 12 picks x 16 of 768 outputs = 0.25 expected."""
from benchmark.lib import shapes_longcat as sl


def read(ctx):
    r = sl.routing(ctx)
    return r["local_pairs"] / r["tokens"] if r and r["tokens"] else None
