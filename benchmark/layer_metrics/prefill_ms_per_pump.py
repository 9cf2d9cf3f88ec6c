"""Device time of the prompt and chunk programs (the modules the program names
``jit_nns_prefill*``) per decode launch in the traced window: what every
decoding slot waits, each pump, for someone else's prompt. Read from the
device: prefill is dispatched asynchronously, so the host's ``nns.pump.prefill``
span holds only its dispatch."""
from benchmark.lib import host_spans
from benchmark.lib import modules as _decode
from benchmark.lib.xplane import module_kind


def read(ctx):
    secs = [s for name, (s, _) in ctx["trace"]["modules"].items()
            if module_kind(name).startswith(host_spans.PREFILL_MODULES)]
    _, steps = _decode.decode_seconds_and_steps(ctx)
    if not secs or not steps:
        return None
    return 1e3 * sum(secs) / (steps / ctx["pump"])
