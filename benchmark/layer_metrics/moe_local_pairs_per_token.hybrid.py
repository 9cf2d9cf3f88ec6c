"""(token, expert) pairs that fell on the experts held here, per token and
expert layer, over the decode steps of the traced window (the
``nns.moe.routing`` instants), for the Kimi-Linear configuration. 8 picks x 64
of 256 outputs = 2.0 expected."""
from benchmark.lib import shapes_kimi_linear as sk


def read(ctx):
    c = sk.counters(ctx) if sk.shape_of(ctx["sizes"]) else None
    return c["local_pairs"] / c["tokens"] if c and c["tokens"] else None
