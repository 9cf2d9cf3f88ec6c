"""The rest of the device's idle share: ``device_idle_pct`` less the parts
inside ``nns.llm.pump`` and ``nns.llm.emit``. What no program span explains,
nothing-to-do included. Also logs the whole split by innermost span, and the
decode launches with the slots live at each, for PERF.md."""
from benchmark.lib import host_spans


def read(ctx):
    pump = host_spans.idle_overlap(ctx, host_spans.PUMP)
    emit = host_spans.idle_overlap(ctx, host_spans.EMIT, minus=host_spans.PUMP)
    w = ctx["trace"]["window_s"]
    if pump is None or emit is None or not w:
        return None
    table = host_spans.idle_by_innermost(ctx) or {}
    host_spans.log("idle seconds by innermost span "
                   f"{ {k: round(v, 4) for k, v in sorted(table.items())} }")
    host_spans.log("decode launches [slots live, device ms] "
                   f"{[[a, round(ms, 2)] for a, ms in host_spans.decode_launches(ctx)]}")
    return 100.0 * (w - ctx["trace"]["busy_s"] - pump - emit) / w
