"""The decode step's expert kernel against its roofline, for the LongCat-Flash
configuration: the least time for the weights of the experts a step hit (the
counter ``experts_hit`` summed over layers, times one expert's bytes at the
bytes each the configuration states; benchmark/lib/shapes_longcat.py) at the
chip's HBM bandwidth, over the ``held_experts_ffn`` ops' device seconds a
step. The steps are the decode launches of the same traced window times the
pump (benchmark/lib/modules.py): the window's edge cuts seconds and steps
alike. None where no such op is on the device (the experts run through
``ragged_dot``)."""
from benchmark.lib import modules as _decode
from benchmark.lib import shapes_longcat as sl

KERNEL = "held_experts_ffn"


def read(ctx):
    s = sl.shape_of(ctx["sizes"])
    r = sl.routing(ctx) if s else None
    secs = sum(t for name, t in ctx["trace"]["ops"].items() if KERNEL in name)
    _, steps = _decode.decode_seconds_and_steps(ctx)
    if not r or not r["pumps"] or not secs or not steps or not ctx["peaks"]:
        return None
    hit = r["experts_hit"] / (r["pumps"] * ctx["pump"])   # per step, over layers
    least = hit * sl.expert_params(s) * s["bytes_per_weight"] \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / steps)
