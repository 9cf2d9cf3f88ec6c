"""Mean ``slot_wait_ms`` of the ``nns.llm.admitted`` events in the traced
window: arrival at ``tensor_llm_serversink`` to a slot claimed, the
back-pressure pumps included. A mean of few (the count is logged), not a tail."""
from benchmark.lib import host_spans


def read(ctx):
    mean, n = host_spans.mean_stat(ctx, "nns.llm.admitted", "slot_wait_ms")
    if n:
        host_spans.log(f"slot_wait_mean_ms over {n} admissions")
    return mean
