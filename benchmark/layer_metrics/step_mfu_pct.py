"""Model FLOPs of every prompt and output token processed in the traced
window over window x the chip's peak FLOP/s. Bounds every kernel's gain:
a kernel taken off the path leaves its roofline silent, this stays."""


def read(ctx):
    w = ctx["trace"]["window_s"]
    if not w or not ctx["peaks"]:
        return None
    flops = ctx["prefill_flops"] + ctx["decode_flops"]
    return 100.0 * flops / (w * ctx["peaks"]["flops_per_s"])
