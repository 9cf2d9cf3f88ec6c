"""Shared by the decode readers: device seconds and steps of the decode
modules in the traced window. A pump launch of N tokens counts N steps."""
from benchmark.lib.xplane import module_kind


def decode_seconds_and_steps(ctx):
    names = set(ctx["trace_names"].get("decode", ()))
    secs = launches = 0.0
    for name, (s, n) in ctx["trace"]["modules"].items():
        if module_kind(name) in names:
            secs += s
            launches += n
    if not launches:
        return None, None
    return secs, launches * ctx["pump"]


def other_seconds(ctx):
    """Device seconds of every module that is not a decode module: prefill,
    chunked prefill, landing staged keys and values, first-token sampling."""
    names = set(ctx["trace_names"].get("decode", ()))
    return sum(s for name, (s, _) in ctx["trace"]["modules"].items()
               if module_kind(name) not in names)
