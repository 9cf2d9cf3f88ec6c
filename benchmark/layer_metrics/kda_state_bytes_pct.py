"""Share of the bytes a decode step must move
(``decode_hbm_roofline_pct.hybrid``'s) that is recurrent state: whether the
cell still measures the mechanism."""
from benchmark.lib import shapes_kimi_linear as sk


def read(ctx):
    nbytes = sk.step_bytes(ctx)
    return 100.0 * nbytes["state"] / sum(nbytes.values()) if nbytes else None
