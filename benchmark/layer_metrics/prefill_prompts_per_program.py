"""Prompts a prompt program completes, over the traced window: the sum of
``prompts`` over the sum of ``programs`` of the ``nns.pump.prefill`` spans
(the host side of every prompt program a pump launches). 1 where every prompt
has a bucket of its own, above 1 where queued prompts share a packed bucket,
under 1 where prompts longer than the bucket are chunked. A program without
the two attributes carries ``buckets`` and ``activated``, which count the same
where nothing is packed. None where no program ran in the window."""
from benchmark.lib import host_spans


def read(ctx):
    stats = [e["stats"] for e in host_spans.spans(ctx, ("nns.pump.prefill",))]
    programs = sum(s.get("programs", s.get("buckets", 0)) for s in stats)
    prompts = sum(s.get("prompts", s.get("activated", 0)) for s in stats)
    if not programs:
        return None
    host_spans.log(f"prefill_prompts_per_program: {prompts} prompts, "
                   f"{programs} programs, {len(stats)} spans")
    return prompts / programs
