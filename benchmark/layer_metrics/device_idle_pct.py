"""1 - busy union of device events / traced window."""


def read(ctx):
    w = ctx["trace"]["window_s"]
    return None if not w else 100.0 * (1.0 - ctx["trace"]["busy_s"] / w)
