"""Share of the (layer, held expert) weights a decode step had to stream, for
the Granite-4.0-H configuration: distinct local experts with a token, summed
over layers and steps, over steps x layers x experts held."""
from benchmark.lib import shapes_granite_hybrid as sg


def read(ctx):
    s = sg.shape_of(ctx["sizes"])
    c = sg.counters(ctx) if s else None
    if not c or not c["steps"]:
        return None
    return 100.0 * c["experts_hit"] / (c["steps"] * s["n_layers"] * s["n_held"])
