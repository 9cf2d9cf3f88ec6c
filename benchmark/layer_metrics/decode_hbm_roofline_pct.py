"""Least time for the bytes one decode step must stream (every weight but the
embedding table, once, plus the keys and values of the live tokens, at the
bytes the configuration's ``served`` block states for its storage) at the
chip's HBM bandwidth, over the measured device time of a decode step.
Bandwidth is the bound that applies: a decode step does two FLOPs per weight
and slot, far under the chip's 240 FLOP per byte."""
from benchmark.lib import shapes

from benchmark.lib import modules as _decode


def read(ctx):
    secs, steps = _decode.decode_seconds_and_steps(ctx)
    if not steps or not secs or not ctx["peaks"]:
        return None
    least = shapes.decode_step_bytes(ctx["sizes"], ctx["live_kv_tokens"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / steps)
