"""Paged (block-table) decode attention as a Pallas TPU kernel.

The flash-style single-pass decode kernel of
:mod:`~nnstreamer_tpu.ops.pallas.decode_attention` generalized to the
nns-kv paged layout (docs/llm-serving.md): instead of one contiguous
``[B, S, KV, D]`` cache row per slot, the K/V live in a shared block
arena ``[N, bs, KV, D]`` behind per-slot block tables ``[B, nb]`` —
and the whole point of this kernel is that the arena is attended
**through the table**, one block per grid step, with NO gathered
contiguous view ever materialized in HBM (the gather → attend →
scatter round trip the jnp gather formulation pays).

Mechanics (grid ``(B, nb)``, k innermost with "arbitrary" semantics):

- the block table and per-slot fill levels ride as SCALAR-PREFETCH
  operands, so each grid step's BlockSpec index map picks the physical
  arena block to DMA (``tables[b, kb]``) before the body runs — each
  live arena block, all its KV heads in one contiguous
  ``(1, bs, KV, d)`` DMA (the block's last two dims are the array's
  own: the (8, 128) rule Mosaic holds blocks to), is read from HBM
  exactly once per slot;
- blocks at or beyond a slot's fill level — including the
  scratch-mapped unallocated table tail — are predicated off with
  ``@pl.when``; partially-filled blocks mask their dead positions to
  softmax weight exactly zero and zero the matching V rows, so
  arbitrary scratch content can never leak into the output;
- the online-softmax scratch (m, l, acc) carries across blocks (the
  decode form of the shared recurrence in ops/pallas/_primitives.py,
  all heads at once; grouped queries laid out [B, g, KV, D] as in
  ops/pallas/decode_attention.py), and the pending token's OWN K/V
  (``fresh_k``/``fresh_v``, not yet in the arena — the batcher lands it
  after the layer scan with one in-place block write) folds in the
  final grid step: it is position ``pos``, the highest live position,
  so the reduction order equals position order;
- int8 arenas pass ``k_scale``/``v_scale`` ``[N, bs, KV]`` (the
  per-token-per-head symmetric scales of models/serving.quantize_kv)
  and dequantize per block in VMEM — HBM traffic stays at the int8
  byte count.

Off-TPU the kernel runs in interpret mode (``_compat`` discipline);
``kv.block_attn.block_attention(impl="auto")`` dispatches between this
kernel (TPU) and the jnp online-softmax reference it is pinned against
in tests/test_kv_block_attn.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.pallas import registry as _registry
from nnstreamer_tpu.ops.pallas._compat import interpret_default
from nnstreamer_tpu.ops.pallas._primitives import (
    decode_attend_block,
    decode_scores,
    decode_softmax_finalize,
    decode_softmax_update,
    load_cache_block,
    online_softmax_init,
)
from nnstreamer_tpu.ops.pallas.decode_attention import (
    group_queries,
    ungroup_heads,
)


def _kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, fk_ref, fv_ref, *rest,
            scale: float, block_k: int, n_b: int, quantized: bool):
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    kb = pl.program_id(1)
    group = q_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    # history length: positions 0..pos-1 live in arena blocks (the
    # pending token's column is the separate fresh operand); clamped to
    # the table's reach so a stale lane can never walk past the arena
    hist = jnp.minimum(pos_ref[b], n_b * block_k)
    k_start = kb * block_k

    @pl.when(k_start < hist)
    def _block():
        decode_attend_block(
            q_ref, load_cache_block(k_ref, ks_ref),   # [bs, KV, d]
            load_cache_block(v_ref, vs_ref), k_start, hist, scale,
            m_ref, l_ref, acc_ref,
        )

    @pl.when(kb == n_b - 1)
    def _final():
        # fold the pending token's own column (position pos — the
        # highest live position, so folding it LAST keeps the reduction
        # in position order), then normalize
        fk = fk_ref[0].astype(jnp.float32)          # [1, KV, d] — always live
        fv = fv_ref[0].astype(jnp.float32)
        for gi in range(group):
            s1 = decode_scores(q_ref[0, gi].astype(jnp.float32), fk, scale)
            _, l, acc = decode_softmax_update(
                s1, fv, m_ref[gi], l_ref[gi], acc_ref[gi]
            )
            o_ref[0, gi] = decode_softmax_finalize(l, acc, o_ref.dtype)


# BlockSpec index maps — module-level so the registered LaunchPlan and
# the live pallas_call share the SAME callables (grid (b, nb), tables +
# pos prefetched). The kv map is where the gather disappears: the
# PREFETCHED table picks the physical arena block each step DMAs.
def _q_index_map(bi, kb, tab_ref, pos_ref):
    return (bi, 0, 0, 0)


def _kv_index_map(bi, kb, tab_ref, pos_ref):
    return (tab_ref[bi, kb], 0, 0, 0)


def _scale_index_map(bi, kb, tab_ref, pos_ref):
    return (tab_ref[bi, kb], 0, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q,
    arena_k,
    arena_v,
    tables,
    pos,
    fresh_k,
    fresh_v,
    k_scale=None,
    v_scale=None,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """q [B,1,H,D]; arena_k/v [N, bs, KV, D] (the kv.gather arena leaves
    of ONE layer, consumed in place; KV ≤ H under grouped-query
    attention — query head hi reads kv head hi//(H/KV), no expansion
    pass); tables [B, nb] int32 block tables; pos [B] int32 HISTORY
    lengths (positions 0..pos-1 attendable from blocks); fresh_k/v
    [B,1,KV,D] the pending token's K/V (column pos) → o [B,1,H,D]
    float32. With ``k_scale``/``v_scale`` [N, bs, KV] the arena
    payloads are int8 and dequantized blockwise in VMEM."""
    b, _, h, d = q.shape
    n_kv = arena_k.shape[2]
    bs = arena_k.shape[1]
    nb = tables.shape[1]
    if h % n_kv:
        raise ValueError(f"query heads {h} not divisible by kv heads {n_kv}")
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    group = h // n_kv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _kernel, scale=scale, block_k=bs, n_b=nb, quantized=quantized,
    )
    q_spec = pl.BlockSpec((1, group, n_kv, d), _q_index_map)
    kv_spec = pl.BlockSpec((1, bs, n_kv, d), _kv_index_map)
    fresh_spec = pl.BlockSpec((1, 1, n_kv, d), _q_index_map)
    in_specs = [q_spec, kv_spec, kv_spec, fresh_spec, fresh_spec]
    operands = [
        tables.astype(jnp.int32), pos.astype(jnp.int32),
        group_queries(q, n_kv), arena_k, arena_v, fresh_k, fresh_v,
    ]
    if quantized:
        scale_spec = pl.BlockSpec((1, bs, n_kv), _scale_index_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
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


def make_paged_attention(interpret: Optional[bool] = None, **kwargs):
    """attn factory for the block-native serving step: real kernel on
    TPU, interpreter elsewhere.

    The returned ``attn(q, k_entry, v_entry, tables, pos, (fk, fv))``
    accepts either float arena leaves or the int8 entries
    ``(payload, scales)`` exactly as kv.block_attn's step bodies hold
    them; ``fk``/``fv`` are the pending token's (already dequantized)
    K/V, folded as the final online-softmax column."""
    if interpret is None:
        interpret = interpret_default()

    def attn(q, cache_k, cache_v, tables, pos, fresh_kv):
        fk, fv = fresh_kv
        if isinstance(cache_k, tuple):
            (k8, ks), (v8, vs) = cache_k, cache_v
            return paged_decode_attention(
                q, k8, v8, tables, pos, fk, fv, k_scale=ks, v_scale=vs,
                interpret=interpret, **kwargs,
            )
        return paged_decode_attention(
            q, cache_k, cache_v, tables, pos, fk, fv,
            interpret=interpret, **kwargs,
        )

    return attn


# -- kernel registration (nns-kscope) ----------------------------------------


def _plan(params):
    b, h, d = params.get("b", 2), params.get("h", 4), params.get("d", 16)
    n_kv = params.get("n_kv", h)
    bs, nb = params["bs"], params["nb"]
    n_blocks = params.get("n_blocks", b * nb)
    dtype = params.get("dtype", "float32")
    group = h // n_kv
    quantized = dtype == "int8"
    float_dtype = "float32" if quantized else dtype
    q_desc = ((b, group, n_kv, d), (1, group, n_kv, d))
    blocks = [
        _registry.BlockDesc("q", "in", *q_desc, float_dtype, _q_index_map),
    ]
    for nm in ("arena_k", "arena_v"):
        blocks.append(_registry.BlockDesc(
            nm, "in", (n_blocks, bs, n_kv, d), (1, bs, n_kv, d), dtype,
            _kv_index_map,
        ))
    for nm in ("fresh_k", "fresh_v"):
        blocks.append(_registry.BlockDesc(
            nm, "in", (b, 1, n_kv, d), (1, 1, n_kv, d), float_dtype,
            _q_index_map,
        ))
    if quantized:
        for nm in ("k_scale", "v_scale"):
            blocks.append(_registry.BlockDesc(
                nm, "in", (n_blocks, bs, n_kv), (1, bs, n_kv), "float32",
                _scale_index_map,
            ))
    blocks.append(_registry.BlockDesc(
        "o", "out", *q_desc, "float32", _q_index_map,
    ))
    import numpy as np

    return _registry.LaunchPlan(
        grid=(b, nb),
        blocks=tuple(blocks),
        scratch=(
            _registry.ScratchDesc("m", (group, n_kv, 1)),
            _registry.ScratchDesc("l", (group, n_kv, 1)),
            _registry.ScratchDesc("acc", (group, n_kv, d)),
        ),
        prefetch=(
            _registry.PrefetchDesc(
                "tables", (b, nb),
                make=lambda: np.arange(b * nb, dtype=np.int32).reshape(b, nb)
                % n_blocks,
            ),
            _registry.PrefetchDesc(
                "pos", (b,),
                make=lambda: np.full((b,), nb * bs, np.int32),
            ),
        ),
        # q·Kᵀ + p·V over nb·bs history columns plus the fresh column
        flops=4 * b * h * (nb * bs + 1) * d,
        notes="arena blocks picked through the prefetched table",
    )


def _case_arrays(params, rng):
    import numpy as np

    b, h, d = params.get("b", 2), params.get("h", 4), params.get("d", 16)
    n_kv = params.get("n_kv", h)
    bs, nb = params["bs"], params["nb"]
    n_blocks = params.get("n_blocks", b * nb)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_blocks)[: b * nb].reshape(b, nb), jnp.int32
    )
    # default fills spread slot positions from empty to full
    default_pos = [(i * nb * bs) // max(1, b - 1) for i in range(b)]
    pos = jnp.asarray(params.get("pos", default_pos), jnp.int32)
    fk = jnp.asarray(rng.standard_normal((b, 1, n_kv, d)), jnp.float32)
    fv = jnp.asarray(rng.standard_normal((b, 1, n_kv, d)), jnp.float32)
    return b, h, d, n_kv, bs, nb, n_blocks, q, tables, pos, fk, fv


def _run_case(params):
    import numpy as np

    from nnstreamer_tpu.kv.block_attn import paged_attention_ref

    rng = np.random.default_rng(3)
    (b, h, d, n_kv, bs, nb, n_blocks,
     q, tables, pos, fk, fv) = _case_arrays(params, rng)
    if params.get("dtype") == "int8":
        ak = jnp.asarray(
            rng.integers(-127, 128, (n_blocks, bs, n_kv, d)), jnp.int8
        )
        av = jnp.asarray(
            rng.integers(-127, 128, (n_blocks, bs, n_kv, d)), jnp.int8
        )
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (n_blocks, bs, n_kv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (n_blocks, bs, n_kv)), jnp.float32)
        got = paged_decode_attention(
            q, ak, av, tables, pos, fk, fv, k_scale=ks, v_scale=vs,
            interpret=interpret_default(),
        )
        want = paged_attention_ref(
            q, ak, av, tables, pos, (fk, fv), k_scale=ks, v_scale=vs
        )
        return got, want, 2e-5
    ak = jnp.asarray(rng.standard_normal((n_blocks, bs, n_kv, d)), jnp.float32)
    av = jnp.asarray(rng.standard_normal((n_blocks, bs, n_kv, d)), jnp.float32)
    got = paged_decode_attention(
        q, ak, av, tables, pos, fk, fv, interpret=interpret_default()
    )
    want = paged_attention_ref(q, ak, av, tables, pos, (fk, fv))
    return got, want, 2e-5


def _probe():
    import numpy as np

    from nnstreamer_tpu.kv.block_attn import block_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 2, 4)), jnp.float32)
    arena = jnp.asarray(rng.standard_normal((4, 2, 2, 4)), jnp.float32)
    tables = jnp.asarray([[0, 1]], jnp.int32)
    pos = jnp.asarray([3], jnp.int32)
    fk = jnp.asarray(rng.standard_normal((1, 1, 2, 4)), jnp.float32)
    fv = jnp.asarray(rng.standard_normal((1, 1, 2, 4)), jnp.float32)
    np.asarray(block_attention(
        q, arena, arena, tables, pos, (fk, fv), impl="pallas"
    ))


_registry.register(_registry.KernelSpec(
    name="paged_decode_attention",
    module=__name__,
    ops=("block_attention", "serving_attention"),
    dtypes=("float32", "bfloat16", "int8"),
    cases=(
        _registry.ShapeCase(
            "b2-full-and-empty", {"bs": 8, "nb": 3, "n_blocks": 8},
            tier1=True,
        ),
        _registry.ShapeCase(
            "gqa-partial-fill",
            {"b": 2, "h": 4, "n_kv": 2, "bs": 8, "nb": 4, "n_blocks": 12,
             "pos": [5, 27]},
            tier1=True,
        ),
        _registry.ShapeCase(
            "int8-arena",
            {"b": 2, "h": 2, "bs": 8, "nb": 3, "n_blocks": 8,
             "dtype": "int8", "pos": [9, 24]},
            tier1=True,
        ),
        _registry.ShapeCase(
            "serve-paged-2048",
            {"b": 8, "h": 8, "d": 128, "bs": 128, "nb": 16, "n_blocks": 128},
        ),
        # the width chip_smoke.py serves (16 heads of 128, 16-token
        # blocks), plus its GQA and int8 variants
        _registry.ShapeCase(
            "serve-h16-d128",
            {"b": 4, "h": 16, "d": 128, "bs": 16, "nb": 8, "n_blocks": 64},
        ),
        _registry.ShapeCase(
            "gqa-16q-4kv-d128",
            {"b": 4, "h": 16, "n_kv": 4, "d": 128, "bs": 16, "nb": 8,
             "n_blocks": 64},
        ),
        _registry.ShapeCase(
            "serve-h16-d128-int8",
            {"b": 4, "h": 16, "d": 128, "bs": 16, "nb": 8, "n_blocks": 64,
             "dtype": "int8"},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
