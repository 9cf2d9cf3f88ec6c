"""Model FLOPs of the traced window over window x the chip's peak, for the
LongCat-Flash configuration: the matmuls of the parameters a token meets
here, its local expert pairs (from the router's counter), decode attention
over the cached context, the head (benchmark/lib/shapes_longcat.py)."""
from benchmark.lib import shapes_longcat as sl


def read(ctx):
    s = sl.shape_of(ctx["sizes"])
    r = sl.routing(ctx) if s else None
    w = ctx["trace"]["window_s"]
    if not s or not r or not r["tokens"] or not w or not ctx["peaks"]:
        return None
    pairs = r["local_pairs"] / r["tokens"]          # per token and layer
    steps = ctx["counters"]["steps"]
    flops = sl.window_flops(s, ctx["prompt_tokens"], 0.0, ctx["out_tokens"], pairs,
                            ctx["live_kv_tokens"] * steps)
    return 100.0 * flops / (w * ctx["peaks"]["flops_per_s"])
