"""Least time for the bytes one decode step of the LongCat-Flash
configuration must stream (attention, dense FFN, router and head weights
once, the experts that were HIT, the latent cache of the live tokens, at the
2 bytes each the configuration states) at the chip's HBM bandwidth, over the
measured device time of a decode step."""
from benchmark.lib import modules as _decode
from benchmark.lib import shapes_longcat as sl


def read(ctx):
    s = sl.shape_of(ctx["sizes"])
    r = sl.routing(ctx) if s else None
    secs, steps = _decode.decode_seconds_and_steps(ctx)
    if not s or not r or not steps or not secs or not ctx["peaks"]:
        return None
    hit = r["experts_hit"] / (r["pumps"] * ctx["pump"])   # per step, over layers
    least = sl.decode_step_bytes(s, ctx["live_kv_tokens"], hit) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / steps)
