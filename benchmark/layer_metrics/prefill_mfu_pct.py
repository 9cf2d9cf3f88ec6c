"""Model FLOPs of the prompts whose first token came in the traced window
over (device time of the non-decode modules x peak FLOP/s). Padding of a
prompt to its bucket is not useful work and is not counted."""
from benchmark.lib import modules as _decode


def read(ctx):
    secs = _decode.other_seconds(ctx)
    if not secs or not ctx["prefill_flops"] or not ctx["peaks"]:
        return None
    return 100.0 * ctx["prefill_flops"] / (secs * ctx["peaks"]["flops_per_s"])
