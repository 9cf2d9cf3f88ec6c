"""Share of the bytes a decode step must move
(``decode_hbm_roofline_pct.ssm``'s) that is Mamba-2 state: whether the cell
still measures the mechanism."""
from benchmark.lib import shapes_granite_hybrid as sg


def read(ctx):
    nbytes = sg.step_bytes(ctx)
    return 100.0 * nbytes["state"] / sum(nbytes.values()) if nbytes else None
