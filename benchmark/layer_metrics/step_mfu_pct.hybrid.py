"""Model FLOPs of the traced window over window x the chip's peak, for the
Kimi-Linear configuration: the matmuls of what a token meets here (attention
by layer kind, the dense FFN, shared expert, router, its local expert pairs
from the router's counter), the recurrence per token and KDA layer, decode
attention over the cached latents, the head
(benchmark/lib/shapes_kimi_linear.py)."""
from benchmark.lib import shapes_kimi_linear as sk


def read(ctx):
    s = sk.shape_of(ctx["sizes"])
    c = sk.counters(ctx) if s else None
    w = ctx["trace"]["window_s"]
    if not s or not c or not c["tokens"] or not w or not ctx["peaks"]:
        return None
    pairs = c["local_pairs"] / c["tokens"]          # per token and expert layer
    flops = sk.window_flops(s, ctx["prompt_tokens"], ctx["out_tokens"], pairs,
                            ctx["live_kv_tokens"] * ctx["counters"]["steps"])
    return 100.0 * flops / (w * ctx["peaks"]["flops_per_s"])
