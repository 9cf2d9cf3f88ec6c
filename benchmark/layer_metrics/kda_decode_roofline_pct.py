"""The recurrence's decode kernel against its roofline: the least time for one
step's state traffic over all KDA layers (each live lane's state once read and
once written at the chip's HBM bandwidth, or the recurrence's FLOPs at its
peak if that is larger; benchmark/lib/shapes_kimi_linear.py) over the kernel's
own device time per step (the ``kda_decode_step`` ops of the trace). None
where the step runs the XLA formulation: no such op is on the device."""
from benchmark.lib import shapes_kimi_linear as sk

KERNEL = "kda_decode_step"


def read(ctx):
    s = sk.shape_of(ctx["sizes"])
    c = sk.counters(ctx) if s else None
    secs = sum(t for name, t in ctx["trace"]["ops"].items() if KERNEL in name)
    steps = ctx["counters"]["steps"]
    if not c or not c["steps"] or not secs or not steps or not ctx["peaks"]:
        return None
    flops, nbytes = sk.kda_decode_cost(s, c["state_updates"] / c["steps"])
    least = max(flops / ctx["peaks"]["flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (secs / steps)
