"""Mean, over every request due in the window, of (first token frame at the
sink - time due). Steadier than the tail, still about 5 % from run to run on
one seed: tokens come in groups of one pump, and where an arrival falls in
the running pump is chance."""


def read(ctx):
    xs = ctx.get("ttft_ms")
    return sum(xs) / len(xs) if xs else None
