"""Share of the (layer, held expert) weights a decode step had to stream:
distinct local experts with a token, summed over layers and steps, over
steps x layers x experts held."""
from benchmark.lib import shapes_longcat as sl


def read(ctx):
    s = sl.shape_of(ctx["sizes"])
    r = sl.routing(ctx) if s else None
    if not r or not r["pumps"]:
        return None
    return 100.0 * r["experts_hit"] / (
        r["pumps"] * ctx["pump"] * s["n_layers"] * s["n_held"])
