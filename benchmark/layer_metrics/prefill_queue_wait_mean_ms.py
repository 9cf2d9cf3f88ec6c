"""Mean ``queue_ms`` of the ``nns.req.prefill_start`` events in the traced
window: a request's wait, slot in hand, for the head of the batcher's prefill
queue. A mean of few (the count is logged), not a tail."""
from benchmark.lib import host_spans


def read(ctx):
    mean, n = host_spans.mean_stat(ctx, "nns.req.prefill_start", "queue_ms")
    if n:
        host_spans.log(f"prefill_queue_wait_mean_ms over {n} requests")
    return mean
