"""Model FLOPs of the traced window over window x the chip's peak, for the
Granite-4.0-H configuration: the matmuls of what a token meets here (mixer or
attention by layer kind, the shared MLP, the router, its local expert pairs
from the router's counter), the scan per token and Mamba-2 layer, decode
attention over the cached keys and values, the head
(benchmark/lib/shapes_granite_hybrid.py)."""
from benchmark.lib import shapes_granite_hybrid as sg


def read(ctx):
    s = sg.shape_of(ctx["sizes"])
    c = sg.counters(ctx) if s else None
    w = ctx["trace"]["window_s"]
    if not s or not c or not c["tokens"] or not w or not ctx["peaks"]:
        return None
    pairs = c["local_pairs"] / c["tokens"]          # per token and layer
    flops = sg.window_flops(s, ctx["prompt_tokens"], ctx["out_tokens"], pairs,
                            ctx["live_kv_tokens"] * ctx["counters"]["steps"])
    return 100.0 * flops / (w * ctx["peaks"]["flops_per_s"])
