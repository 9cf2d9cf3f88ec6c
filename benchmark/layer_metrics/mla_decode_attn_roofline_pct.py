"""The latent decode attention kernel against its roofline: the least time
for the reads of one decode step over all 2L attentions (the larger of their
FLOPs at the chip's peak and the live tokens' latent bytes at its HBM
bandwidth; benchmark/lib/shapes_longcat.py) over the kernel's own device
time per step (the ``mla_paged_decode_attention`` ops of the trace). None
where the step runs the XLA formulation: no such op is on the device."""
from benchmark.lib import shapes_longcat as sl

KERNEL = "mla_paged_decode_attention"


def read(ctx):
    s = sl.shape_of(ctx["sizes"])
    secs = sum(t for name, t in ctx["trace"]["ops"].items() if KERNEL in name)
    steps = ctx["counters"]["steps"]
    if not s or not secs or not steps or not ctx["peaks"]:
        return None
    flops, nbytes = sl.mla_decode_attention_cost(s, ctx["live_kv_tokens"])
    least = max(flops / ctx["peaks"]["flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (secs / steps)
