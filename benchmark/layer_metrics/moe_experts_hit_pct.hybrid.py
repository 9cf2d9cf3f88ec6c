"""Share of the (expert layer, held expert) weights a decode step had to
stream, for the Kimi-Linear configuration: distinct local experts with a
token, summed over layers and steps, over steps x expert layers x experts
held."""
from benchmark.lib import shapes_kimi_linear as sk


def read(ctx):
    s = sk.shape_of(ctx["sizes"])
    c = sk.counters(ctx) if s else None
    if not c or not c["steps"]:
        return None
    return 100.0 * c["experts_hit"] / (c["steps"] * s["n_expert_layers"] * s["n_held"])
