"""Single-token decode attention as a Pallas TPU kernel.

The serving hot loop (models/serving.py batched_decode_step) attends one
query token per slot against that slot's KV cache. Decode attention is
memory-bound: the FLOPs are trivial, the cost is streaming the cache out
of HBM. An unfused formulation reads K for the scores and V for the
weighted sum as two separate passes with a [B,H,1,S] score tensor in
between; this kernel is the flash-style single pass — each cache block is
read once, scores never leave VMEM, and the per-slot fill level arrives
as a scalar-prefetch operand, so masking costs no extra HBM tensor.

The kernel indexes the serving cache layout [B, S, KV, D] directly via
BlockSpecs (grid (B, k-blocks), block (1, bk, KV, d): every KV head of
``bk`` positions in one contiguous DMA, the block's last two dims being
the array's own — the (8, 128) rule Mosaic holds blocks to) — no
transpose, no pad, no bias materialization on the host side; ``pos``
[B] rides in SMEM. k innermost with "arbitrary" semantics (sequential
on TPU), the online-softmax scratch (m, l, acc) carried across k
iterations — the decode form of the shared recurrence in
ops/pallas/_primitives.py, all heads at once. Blocks entirely beyond a
slot's fill level are predicated off with @pl.when.

Grouped-query attention: the wrapper lays the (tiny) query out as
[B, g, KV, D] — query head ``kv*g + gi`` at ``[gi, kv]`` — so each of
the g query rows a KV head serves is one [KV, D] tile in the cache
block's own layout; the kernel walks gi over one resident cache block.

Int8 caches: pass ``k_scale``/``v_scale`` [B, S, KV] (per-token-per-head
symmetric scales, kv/gather.quantize_kv layout) and int8 cache
arrays — the kernel dequantizes per block in VMEM, so HBM traffic stays
at the int8 byte count (the whole point of quantizing the cache: 4× less
cache streaming per decode step than f32).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.pallas import registry as _registry
from nnstreamer_tpu.ops.pallas._compat import interpret_default, pallas_ok
from nnstreamer_tpu.ops.pallas._primitives import (
    NEG_INF,
    decode_attend_block,
    decode_softmax_finalize,
    load_cache_block,
    online_softmax_init,
)


def _kernel(pos_ref, q_ref, k_ref, v_ref, *rest,
            scale: float, block_k: int, n_k: int, s_len: int,
            quantized: bool):
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    k_start = ki * block_k
    # positions 0..pos inclusive are attendable; a windowed ring passes
    # ABSOLUTE pos, so after a wrap pos+1 exceeds the cache length and
    # every row is live — clamp to the static cache length so the tail
    # block's pad rows (positions in [s_len, n_k*block_k)) stay masked
    # instead of streaming pad garbage into the softmax.
    live_len = jnp.minimum(pos_ref[b] + 1, s_len)

    @pl.when(k_start < live_len)
    def _block():
        decode_attend_block(
            q_ref, load_cache_block(k_ref, ks_ref),   # [bk, KV, d]
            load_cache_block(v_ref, vs_ref), k_start, live_len, scale,
            m_ref, l_ref, acc_ref,
        )

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[0] = decode_softmax_finalize(l_ref[:], acc_ref[:], o_ref.dtype)


def _pick_block(s_len: int, block_k: int) -> Tuple[int, int]:
    """(block size, grid length) covering s_len with ceil-division.

    Blocks need not divide the cache length: Pallas pads the tail block,
    and the kernel's ``position < live_len`` mask (live_len ≤ s_len)
    already neutralizes the pad rows — so a prime or odd cache length
    keeps full-width blocks instead of degenerating to 1-row blocks."""
    bk = min(block_k, s_len)
    return bk, -(-s_len // bk)


def group_queries(q, n_kv: int):
    """q [B, 1, H, D] → [B, g, KV, D]: query head ``kv*g + gi`` lands at
    ``[gi, kv]``, one [KV, D] tile per group member in the cache
    block's own (heads on sublanes) layout. g = H / KV; a no-op
    relabelling under plain multi-head attention (g = 1)."""
    b, _, h, d = q.shape
    return q.reshape(b, n_kv, h // n_kv, d).transpose(0, 2, 1, 3)


def ungroup_heads(o):
    """Inverse of :func:`group_queries`: o [B, g, KV, D] → [B, 1, H, D]."""
    b, g, n_kv, d = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, 1, g * n_kv, d)


# BlockSpec index maps — module-level so the registered LaunchPlan and
# the live pallas_call share the SAME callables (grid (b, k-blocks),
# pos prefetched).
def _q_index_map(bi, kk, pos_ref):
    return (bi, 0, 0, 0)


def _kv_index_map(bi, kk, pos_ref):
    return (bi, kk, 0, 0)


def _scale_index_map(bi, kk, pos_ref):
    return (bi, kk, 0)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention(
    q,
    cache_k,
    cache_v,
    pos,
    k_scale=None,
    v_scale=None,
    scale: Optional[float] = None,
    block_k: int = 128,
    interpret: bool = False,
):
    """q [B,1,H,D], cache_k/v [B,S,KV,D] (the serving layout, consumed
    in place; KV ≤ H under grouped-query attention — query head hi reads
    kv head hi//(H/KV), no expansion pass), pos [B] → o [B,1,H,D]
    float32. Positions > pos[b] are masked per slot. With
    ``k_scale``/``v_scale`` [B,S,KV] the cache arrays are int8 and
    dequantized blockwise in VMEM."""
    b, _, h, d = q.shape
    s_len = cache_k.shape[1]
    n_kv = cache_k.shape[2]
    if h % n_kv:
        raise ValueError(f"query heads {h} not divisible by kv heads {n_kv}")
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    group = h // n_kv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bk, n_k = _pick_block(s_len, block_k)
    kernel = functools.partial(
        _kernel, scale=scale, block_k=bk, n_k=n_k, s_len=s_len,
        quantized=quantized,
    )
    q_spec = pl.BlockSpec((1, group, n_kv, d), _q_index_map)
    kv_spec = pl.BlockSpec((1, bk, n_kv, d), _kv_index_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [pos.astype(jnp.int32), group_queries(q, n_kv), cache_k, cache_v]
    if quantized:
        scale_spec = pl.BlockSpec((1, bk, n_kv), _scale_index_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, n_kv, 1), jnp.float32),
            pltpu.VMEM((group, n_kv, 1), jnp.float32),
            pltpu.VMEM((group, n_kv, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, group, n_kv, d), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return ungroup_heads(out)


def decode_attention_ref(q, cache_k, cache_v, pos, k_scale=None,
                         v_scale=None, scale: Optional[float] = None):
    """jnp masked-softmax reference of the decode kernel: q [B,1,H,D],
    cache [B,S,KV,D] (int8 with ``k_scale``/``v_scale`` [B,S,KV]), pos
    [B] → [B,1,H,D] float32. Same clamp as the kernel: positions
    0..min(pos, S-1) attendable (a wrapped ring passes absolute pos).
    GQA folds query heads over the compact KV heads, no expansion."""
    b, _, h, d = q.shape
    s_len = cache_k.shape[1]
    n_kv = cache_k.shape[2]
    g = h // n_kv
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    ck = cache_k.astype(jnp.float32)
    cv = cache_v.astype(jnp.float32)
    if k_scale is not None:
        ck = ck * k_scale[..., None]
        cv = cv * v_scale[..., None]
    q5 = q.astype(jnp.float32)[:, 0].reshape(b, n_kv, g, d)
    s = jnp.einsum("bkgd,bskd->bkgs", q5, ck) * sc
    live_len = jnp.minimum(pos + 1, s_len)
    live = jnp.arange(s_len)[None, :] < live_len[:, None]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cv)
    return o.reshape(b, 1, h, d)


def make_decode_attention(interpret: Optional[bool] = None, **kwargs):
    """attn factory: real kernel on TPU, interpreter elsewhere.

    The returned ``attn(q, ck, cv, pos)`` accepts either float cache
    arrays or the serving int8 cache entries ``(ck8, k_scale)`` /
    ``(cv8, v_scale)`` (kv/gather.py quantize_kv layout). Each
    trace consults the registry's dtype support (_compat.pallas_ok) and
    degrades to :func:`decode_attention_ref` with a logged reason
    instead of a trace-time Mosaic error; the resolved choice lands in
    the dispatch tally as op "decode_attention"."""
    from nnstreamer_tpu.ops.dispatch import record as _record_dispatch

    if interpret is None:
        interpret = interpret_default()

    def attn(q, cache_k, cache_v, pos):
        payload = cache_k[0] if isinstance(cache_k, tuple) else cache_k
        ok, _ = pallas_ok("decode_attention", payload.dtype)
        _record_dispatch("decode_attention", "pallas" if ok else "jnp")
        if isinstance(cache_k, tuple):
            (k8, ks), (v8, vs) = cache_k, cache_v
            fn = decode_attention if ok else decode_attention_ref
            kw = dict(kwargs) if ok else {
                k: v for k, v in kwargs.items() if k == "scale"
            }
            if ok:
                kw["interpret"] = interpret
            return fn(q, k8, v8, pos, k_scale=ks, v_scale=vs, **kw)
        if not ok:
            return decode_attention_ref(
                q, cache_k, cache_v, pos, scale=kwargs.get("scale")
            )
        return decode_attention(q, cache_k, cache_v, pos,
                                interpret=interpret, **kwargs)

    return attn


# -- kernel registration (nns-kscope) ----------------------------------------


def _plan(params):
    b, h, d = params.get("b", 2), params.get("h", 4), params.get("d", 16)
    n_kv = params.get("n_kv", h)
    s_len = params["s_len"]
    dtype = params.get("dtype", "float32")
    group = h // n_kv
    bk, n_k = _pick_block(s_len, params.get("block_k", 128))
    quantized = dtype == "int8"
    q_desc = ((b, group, n_kv, d), (1, group, n_kv, d))
    blocks = [
        _registry.BlockDesc(
            "q", "in", *q_desc, dtype if not quantized else "float32",
            _q_index_map,
        ),
        _registry.BlockDesc(
            "cache_k", "in", (b, s_len, n_kv, d), (1, bk, n_kv, d), dtype,
            _kv_index_map,
        ),
        _registry.BlockDesc(
            "cache_v", "in", (b, s_len, n_kv, d), (1, bk, n_kv, d), dtype,
            _kv_index_map,
        ),
    ]
    if quantized:
        for nm in ("k_scale", "v_scale"):
            blocks.append(_registry.BlockDesc(
                nm, "in", (b, s_len, n_kv), (1, bk, n_kv), "float32",
                _scale_index_map,
            ))
    blocks.append(_registry.BlockDesc(
        "o", "out", *q_desc, "float32", _q_index_map,
    ))
    import numpy as np

    return _registry.LaunchPlan(
        grid=(b, n_k),
        blocks=tuple(blocks),
        scratch=(
            _registry.ScratchDesc("m", (group, n_kv, 1)),
            _registry.ScratchDesc("l", (group, n_kv, 1)),
            _registry.ScratchDesc("acc", (group, n_kv, d)),
        ),
        prefetch=(
            _registry.PrefetchDesc(
                "pos", (b,),
                make=lambda: np.full((b,), s_len - 1, np.int32),
            ),
        ),
        # q·Kᵀ + p·V: 2·s·d each per (slot, head)
        flops=4 * b * h * s_len * d,
        notes="memory-bound: cache streaming dominates",
    )


def _run_case(params):
    import numpy as np

    rng = np.random.default_rng(1)
    b, h, d = params.get("b", 3), params.get("h", 4), params.get("d", 16)
    n_kv = params.get("n_kv", h)
    s_len, block_k = params["s_len"], params.get("block_k", 128)
    dtype = params.get("dtype", "float32")
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    # default fills spread slot positions from empty to full
    default_pos = [(i * (s_len - 1)) // max(1, b - 1) for i in range(b)]
    pos = jnp.asarray(params.get("pos", default_pos), jnp.int32)
    if dtype == "int8":
        ck = jnp.asarray(rng.integers(-127, 128, (b, s_len, n_kv, d)), jnp.int8)
        cv = jnp.asarray(rng.integers(-127, 128, (b, s_len, n_kv, d)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (b, s_len, n_kv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (b, s_len, n_kv)), jnp.float32)
        got = decode_attention(q, ck, cv, pos, k_scale=ks, v_scale=vs,
                               block_k=block_k, interpret=interpret_default())
        want = decode_attention_ref(q, ck, cv, pos, k_scale=ks, v_scale=vs)
        return got, want, 2e-5
    cast = jnp.dtype(dtype)
    qd = q.astype(cast)
    ck = jnp.asarray(rng.standard_normal((b, s_len, n_kv, d)), jnp.float32).astype(cast)
    cv = jnp.asarray(rng.standard_normal((b, s_len, n_kv, d)), jnp.float32).astype(cast)
    got = decode_attention(qd, ck, cv, pos, block_k=block_k, interpret=interpret_default())
    want = decode_attention_ref(qd, ck, cv, pos)
    return got, want, (2e-2 if cast == jnp.bfloat16 else 2e-5)


def _probe():
    import numpy as np

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((1, 16, 2, 8)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((1, 16, 2, 8)), jnp.float32)
    pos = jnp.asarray([7], jnp.int32)
    np.asarray(make_decode_attention()(q, ck, cv, pos))


_registry.register(_registry.KernelSpec(
    name="decode_attention",
    module=__name__,
    ops=("decode_attention", "serving_attention"),
    dtypes=("float32", "bfloat16", "int8"),
    cases=(
        # the parity grid tests/test_pallas.py parametrizes over; the
        # non-dividing lengths pin ceil-covered tail blocks (ADVICE r2)
        _registry.ShapeCase("s64-bk16", {"s_len": 64, "block_k": 16}, tier1=True),
        _registry.ShapeCase("s48-bk16", {"s_len": 48, "block_k": 16}),
        _registry.ShapeCase("s40-bk128", {"s_len": 40, "block_k": 128}, tier1=True),
        _registry.ShapeCase("s97-bk32", {"s_len": 97, "block_k": 32}, tier1=True),
        _registry.ShapeCase("s130-bk128", {"s_len": 130, "block_k": 128}),
        _registry.ShapeCase("s33-bk16", {"s_len": 33, "block_k": 16}),
        _registry.ShapeCase(
            "gqa-int8",
            {"b": 2, "h": 4, "n_kv": 2, "s_len": 48, "block_k": 16,
             "dtype": "int8", "pos": [11, 40]},
            tier1=True,
        ),
        _registry.ShapeCase(
            "bf16",
            {"b": 2, "h": 2, "s_len": 32, "block_k": 16, "dtype": "bfloat16",
             "pos": [5, 20]},
        ),
        _registry.ShapeCase(
            "serve-2048", {"b": 8, "h": 8, "d": 128, "s_len": 2048},
        ),
        # the width chip_smoke.py serves (16 heads of 128), fp and int8
        _registry.ShapeCase(
            "serve-h16-d128", {"b": 4, "h": 16, "d": 128, "s_len": 2048},
        ),
        _registry.ShapeCase(
            "serve-h16-d128-int8",
            {"b": 4, "h": 16, "d": 128, "s_len": 512, "dtype": "int8"},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
