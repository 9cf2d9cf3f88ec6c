"""Least time for the bytes one decode step of the Kimi-Linear configuration
must move (the weights outside the routed experts and the head once, the
experts that were HIT, the latents of the live tokens, the recurrent state of
the live lanes once read and once written, at the bytes the configuration
states) at the chip's HBM bandwidth, over the measured device time of a decode
step."""
from benchmark.lib import modules as _decode
from benchmark.lib import shapes_kimi_linear as sk


def read(ctx):
    nbytes = sk.step_bytes(ctx)
    secs, steps = _decode.decode_seconds_and_steps(ctx)
    if not nbytes or not steps or not secs or not ctx["peaks"]:
        return None
    least = sum(nbytes.values()) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / steps)
