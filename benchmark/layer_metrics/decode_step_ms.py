"""Device time of the decode modules over the decode steps they ran."""
from benchmark.lib import modules as _decode


def read(ctx):
    secs, steps = _decode.decode_seconds_and_steps(ctx)
    return None if not steps else 1e3 * secs / steps
