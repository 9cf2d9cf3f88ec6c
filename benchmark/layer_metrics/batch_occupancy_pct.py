"""Tokens the decode steps emitted over the slots they ran:
delta tokens_emitted / (delta steps x n_slots), from the batcher's counters
over the traced window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return 100.0 * c["tokens_emitted"] / (c["steps"] * ctx["n_slots"])
