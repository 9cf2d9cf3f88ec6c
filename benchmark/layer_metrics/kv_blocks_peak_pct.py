"""Peak of the KV pool's blocks in use (``nns_kv_blocks_in_use``, sampled by
the driver over the window) over the arena's blocks."""


def read(ctx):
    if not ctx.get("kv_blocks_total") or not ctx.get("kv_blocks_peak"):
        return None
    return 100.0 * ctx["kv_blocks_peak"] / ctx["kv_blocks_total"]
