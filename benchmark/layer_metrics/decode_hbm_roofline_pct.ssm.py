"""Least time for the bytes one decode step of the Granite-4.0-H configuration
must move (the weights outside the routed experts and the head once, the
experts that were HIT, the keys and values of the live tokens, the state of
the live lanes once read and once written, at the bytes the configuration
states) at the chip's HBM bandwidth, over the measured device time of a decode
step."""
from benchmark.lib import modules as _decode
from benchmark.lib import shapes_granite_hybrid as sg


def read(ctx):
    nbytes = sg.step_bytes(ctx)
    secs, steps = _decode.decode_seconds_and_steps(ctx)
    if not nbytes or not steps or not secs or not ctx["peaks"]:
        return None
    least = sum(nbytes.values()) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / steps)
