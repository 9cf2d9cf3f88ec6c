"""The scan's decode kernel against its roofline: the least time for one
step's state traffic over all Mamba-2 layers (each live lane's state once read
and once written at the chip's HBM bandwidth, or the scan's FLOPs at its peak
if that is larger; benchmark/lib/shapes_granite_hybrid.py) over the kernel's
own device time per step (the ``ssm_decode_step`` ops of the trace). None
where the step runs the XLA formulation: no such op is on the device."""
from benchmark.lib import shapes_granite_hybrid as sg

KERNEL = "ssm_decode_step"


def read(ctx):
    s = sg.shape_of(ctx["sizes"])
    c = sg.counters(ctx) if s else None
    secs = sum(t for name, t in ctx["trace"]["ops"].items() if KERNEL in name)
    steps = ctx["counters"]["steps"]
    if not c or not c["steps"] or not secs or not steps or not ctx["peaks"]:
        return None
    flops, nbytes = sg.ssm_decode_cost(s, c["state_updates"] / c["steps"])
    least = max(flops / ctx["peaks"]["flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (secs / steps)
