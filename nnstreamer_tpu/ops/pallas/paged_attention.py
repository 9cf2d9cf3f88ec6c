"""Paged (block-table) decode attention as a Pallas TPU kernel.

The flash-style single-pass decode kernel of
:mod:`~nnstreamer_tpu.ops.pallas.decode_attention` generalized to the
nns-kv paged layout (docs/llm-serving.md): instead of one contiguous
``[B, S, KV, D]`` cache row per slot, the K/V live in a shared block
arena ``[L, N, bs, KV, D]`` behind per-slot block tables ``[B, nb]`` —
and the whole point of this kernel is that the arena is attended
**through the table**, live blocks only, with NO gathered contiguous
view ever materialized in HBM (the take → write → select → attend
passes the XLA formulation of kv/block_attn.py pays per layer and step).

Mechanics (grid ``(B, ceil(nb / C))``, chunk axis innermost with
"arbitrary" semantics; C logical blocks a grid step, see
:func:`blocks_per_step`):

- the block table, the per-slot fill levels and the LAYER index ride as
  SCALAR-PREFETCH operands. The arena leaf is passed WHOLE: the layer
  scan of ``batched_decode_step_block`` hands the same ``[L, N, ...]``
  array to every layer and the index maps pick ``(layer, block)``, so no
  layer-sized slice is ever copied in front of the custom call;
- each grid step holds C K operands and C V operands, one arena block
  each — all its KV heads in one contiguous ``(bs, KV, d)`` DMA (the
  block's last two dims are the array's own: the (8, 128) rule Mosaic
  holds blocks to). Operand ``i`` of chunk ``kb`` is logical block
  ``kb*C + i``; once that is at or past the slot's fill the index map
  CLAMPS to the last chunk in which operand ``i`` was live (the
  pipeline sees an unchanged block index and issues no DMA), or to
  scratch block 0 where it never was: a live arena block is read from
  HBM exactly once per slot, a dead table entry never. Inactive lanes
  pass fill level 0 and read nothing;
- the body skips dead blocks under ``@pl.when``; the partially-filled
  block masks its dead positions to softmax weight exactly zero and
  zeroes the matching V rows, so arbitrary stale content can never leak
  into the output;
- the online-softmax scratch (m, l, acc) carries across blocks (the
  decode form of the shared recurrence in ops/pallas/_primitives.py,
  all heads at once; grouped queries laid out [B, g, KV, D] as in
  ops/pallas/decode_attention.py: a block is loaded once and every group
  member folds it), and the pending token's OWN K/V
  (``fresh_k``/``fresh_v``, not yet in the arena — the batcher lands it
  after the layer scan with one in-place block write) folds in the
  final grid step: it is position ``pos``, the highest live position,
  so the reduction order equals position order;
- int8 arenas pass ``k_scale``/``v_scale`` ``[L, N, bs, KV]`` (the
  per-token-per-head symmetric scales of kv/gather.quantize_kv)
  and dequantize per block in VMEM — HBM traffic stays at the int8
  byte count.

What it costs (PERF.md §6, PR 26): the pipeline evaluates every
operand's index map at every grid step, dead or live, about 0.1 us
each — a floor of 2 * B * nb of them per layer whatever is live (3.5 ms
a decode step at the benchmark's shapes), above which the vector unit
folds live blocks at 300 GB/s (MHA) or 100 GB/s (4 queries a KV head).

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

# one grid step's K (and V) chunk: at most this many bytes and tokens.
# Double-buffered K + V is 4x the bytes: 4 MiB of the 16 MiB a kernel
# may hold (32 blocks of olmo-1b's shape do not fit), and the unrolled
# body stays a few thousand vector ops. 4, 8 and 16 blocks a step
# measured within 5 % of each other on the chip (PERF.md §6, PR 26)
CHUNK_BYTES = 1 << 20
CHUNK_TOKENS = 256


def blocks_per_step(bs: int, n_kv: int, d: int, itemsize: int,
                    nb: int) -> int:
    """Logical blocks one grid step attends: as many as fit
    ``CHUNK_BYTES`` of K payload and ``CHUNK_TOKENS`` positions, from
    the block's own shape (olmo-1b's 16 x 16 x 128 float32 blocks: 8 =
    128 tokens; mistral-7b's 16 x 8 x 128: 16 = 256 tokens)."""
    block_bytes = bs * n_kv * d * itemsize
    return max(1, min(nb, CHUNK_TOKENS // bs, CHUNK_BYTES // block_bytes))


def _kernel(tab_ref, fill_ref, layer_ref, q_ref, fk_ref, fv_ref, *rest,
            scale: float, block_k: int, chunk: int, quantized: bool):
    k_refs, rest = rest[:chunk], rest[chunk:]
    v_refs, rest = rest[:chunk], rest[chunk:]
    ks_refs = vs_refs = (None,) * chunk
    if quantized:
        ks_refs, rest = rest[:chunk], rest[chunk:]
        vs_refs, rest = rest[:chunk], rest[chunk:]
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    # fill level: positions 0..fill-1 live in arena blocks (the pending
    # token's column is the separate fresh operand); the wrapper clamps
    # it to the table's reach and zeroes it for inactive lanes
    fill = fill_ref[b]
    for i in range(chunk):
        k_start = (kb * chunk + i) * block_k

        @pl.when(k_start < fill)
        def _block(i=i, k_start=k_start):
            decode_attend_block(
                q_ref,
                load_cache_block(k_refs[i], ks_refs[i]),   # [bs, KV, d]
                load_cache_block(v_refs[i], vs_refs[i]),
                k_start, fill, scale, m_ref, l_ref, acc_ref,
            )

    @pl.when(kb == pl.num_programs(1) - 1)
    def _final():
        # fold the pending token's own column (position pos — the
        # highest live position, so folding it LAST keeps the reduction
        # in position order), then normalize
        fk = fk_ref[0].astype(jnp.float32)          # [1, KV, d] — always live
        fv = fv_ref[0].astype(jnp.float32)
        for gi in range(q_ref.shape[1]):
            s1 = decode_scores(q_ref[0, gi].astype(jnp.float32), fk, scale)
            _, l, acc = decode_softmax_update(
                s1, fv, m_ref[gi], l_ref[gi], acc_ref[gi]
            )
            o_ref[0, gi] = decode_softmax_finalize(l, acc, o_ref.dtype)


# BlockSpec index maps — module-level so the registered LaunchPlan and
# the live pallas_call share the SAME callables (grid (b, chunk),
# tables + fill levels + layer prefetched).
def _q_index_map(bi, kb, tab_ref, fill_ref, layer_ref):
    return (bi, 0, 0, 0)


def _arena_index_map(bi, kb, tab_ref, fill_ref, layer_ref, *, i, chunk,
                     block_k, minor):
    """Where the gather disappears: the PREFETCHED table picks the
    physical arena block operand ``i`` of chunk ``kb`` DMAs — logical
    block ``kb*chunk + i`` while that is under the slot's fill; past it,
    the block this operand held in its last live chunk (no new DMA);
    scratch block 0 for an operand the slot never fills. ``minor`` is
    the count of whole trailing dims (3 payload, 2 scales)."""
    n_live = (fill_ref[bi] + block_k - 1) // block_k
    behind = n_live - 1 - i       # live blocks at or past operand i's first
    if isinstance(behind, jax.Array):     # traced, inside pallas_call
        lb = i + chunk * jnp.minimum(kb, jnp.maximum(behind, 0) // chunk)
        phys = jnp.where(behind < 0, 0, tab_ref[bi, lb])
    else:                                 # plain ints, the kscope enumerator
        lb = i + chunk * min(kb, max(behind, 0) // chunk)
        phys = 0 if behind < 0 else tab_ref[bi, lb]
    return (layer_ref[0], phys) + (0,) * minor


@functools.partial(
    jax.jit, static_argnames=("scale", "chunk", "interpret")
)
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
    layer=None,
    scale: Optional[float] = None,
    chunk: Optional[int] = None,
    interpret: bool = False,
):
    """q [B,1,H,D]; arena_k/v the kv.gather arena leaves, consumed in
    place: WHOLE ``[L, N, bs, KV, D]`` with ``layer`` the (traced) int32
    index of the layer to attend, or one layer's ``[N, bs, KV, D]``
    (``layer`` None). KV ≤ H under grouped-query attention — query head
    hi reads kv head hi//(H/KV), no expansion pass. tables [B, nb] int32
    block tables; pos [B] int32 HISTORY lengths (positions 0..pos-1
    attendable from blocks; 0 for a lane that must read nothing);
    fresh_k/v [B,1,KV,D] the pending token's K/V (column pos) → o
    [B,1,H,D] float32. With ``k_scale``/``v_scale`` (the arena's
    ``[..., bs, KV]`` scale leaves) the payloads are int8 and
    dequantized blockwise in VMEM. ``chunk`` overrides
    :func:`blocks_per_step` (tests)."""
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    if layer is None:
        arena_k, arena_v = arena_k[None], arena_v[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    b, _, h, d = q.shape
    bs, n_kv = arena_k.shape[2], arena_k.shape[3]
    nb = tables.shape[1]
    if h % n_kv:
        raise ValueError(f"query heads {h} not divisible by kv heads {n_kv}")
    group = h // n_kv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    chunk = min(nb, chunk) if chunk else blocks_per_step(
        bs, n_kv, d, arena_k.dtype.itemsize, nb
    )
    kernel = functools.partial(
        _kernel, scale=scale, block_k=bs, chunk=chunk, quantized=quantized,
    )
    geom = dict(chunk=chunk, block_k=bs)
    q_spec = pl.BlockSpec((1, group, n_kv, d), _q_index_map)
    fresh_spec = pl.BlockSpec((1, 1, n_kv, d), _q_index_map)
    kv_specs = [
        pl.BlockSpec(
            (None, 1, bs, n_kv, d),
            functools.partial(_arena_index_map, i=i, minor=3, **geom),
        )
        for i in range(chunk)
    ]
    in_specs = [q_spec, fresh_spec, fresh_spec] + kv_specs + kv_specs
    operands = [
        tables.astype(jnp.int32),
        jnp.clip(pos.astype(jnp.int32), 0, nb * bs),
        jnp.asarray(layer, jnp.int32).reshape(1),
        group_queries(q, n_kv), fresh_k, fresh_v,
    ] + [arena_k] * chunk + [arena_v] * chunk
    if quantized:
        scale_specs = [
            pl.BlockSpec(
                (None, 1, bs, n_kv),
                functools.partial(_arena_index_map, i=i, minor=2, **geom),
            )
            for i in range(chunk)
        ]
        in_specs += scale_specs + scale_specs
        operands += [k_scale] * chunk + [v_scale] * chunk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, pl.cdiv(nb, chunk)),
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

    The returned ``attn(q, k_entry, v_entry, tables, pos, (fk, fv),
    layer=None)`` accepts either float arena leaves or the int8 entries
    ``(payload, scales)`` exactly as kv.block_attn's step bodies hold
    them — whole ``[L, N, ...]`` leaves with ``layer`` the index to
    attend, or one layer's leaves without; ``fk``/``fv`` are the pending
    token's (already dequantized) K/V, folded as the final
    online-softmax column."""
    if interpret is None:
        interpret = interpret_default()

    def attn(q, cache_k, cache_v, tables, pos, fresh_kv, layer=None):
        fk, fv = fresh_kv
        if isinstance(cache_k, tuple):
            (k8, ks), (v8, vs) = cache_k, cache_v
            return paged_decode_attention(
                q, k8, v8, tables, pos, fk, fv, k_scale=ks, v_scale=vs,
                layer=layer, interpret=interpret, **kwargs,
            )
        return paged_decode_attention(
            q, cache_k, cache_v, tables, pos, fk, fv, layer=layer,
            interpret=interpret, **kwargs,
        )

    return attn


# -- kernel registration (nns-kscope) ----------------------------------------


def _case_geometry(params):
    b, h, d = params.get("b", 2), params.get("h", 4), params.get("d", 16)
    n_kv = params.get("n_kv", h)
    bs, nb = params["bs"], params["nb"]
    n_blocks = params.get("n_blocks", b * nb)
    dtype = params.get("dtype", "float32")
    itemsize = 1 if dtype == "int8" else 4
    chunk = min(nb, params.get("chunk") or blocks_per_step(
        bs, n_kv, d, itemsize, nb))
    return b, h, d, n_kv, bs, nb, n_blocks, dtype, chunk


def _plan(params):
    import numpy as np

    b, h, d, n_kv, bs, nb, n_blocks, dtype, chunk = _case_geometry(params)
    layers = params.get("layers", 1)
    group = h // n_kv
    quantized = dtype == "int8"
    float_dtype = "float32" if quantized else dtype
    fill = np.clip(
        np.asarray(params.get("pos", [nb * bs] * b), np.int64), 0, nb * bs
    )
    geom = dict(chunk=chunk, block_k=bs)
    q_desc = ((b, group, n_kv, d), (1, group, n_kv, d))
    blocks = [
        _registry.BlockDesc("q", "in", *q_desc, float_dtype, _q_index_map),
    ]
    for nm in ("fresh_k", "fresh_v"):
        blocks.append(_registry.BlockDesc(
            nm, "in", (b, 1, n_kv, d), (1, 1, n_kv, d), float_dtype,
            _q_index_map,
        ))
    for nm in ("arena_k", "arena_v"):
        for i in range(chunk):
            blocks.append(_registry.BlockDesc(
                f"{nm}{i}", "in", (layers, n_blocks, bs, n_kv, d),
                (1, 1, bs, n_kv, d), dtype,
                functools.partial(_arena_index_map, i=i, minor=3, **geom),
            ))
    if quantized:
        for nm in ("k_scale", "v_scale"):
            for i in range(chunk):
                blocks.append(_registry.BlockDesc(
                    f"{nm}{i}", "in", (layers, n_blocks, bs, n_kv),
                    (1, 1, bs, n_kv), "float32",
                    functools.partial(_arena_index_map, i=i, minor=2, **geom),
                ))
    blocks.append(_registry.BlockDesc(
        "o", "out", *q_desc, "float32", _q_index_map,
    ))
    return _registry.LaunchPlan(
        grid=(b, -(-nb // chunk)),
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
                "fill", (b,), make=lambda: fill.astype(np.int32),
            ),
            _registry.PrefetchDesc(
                "layer", (1,),
                make=lambda: np.full((1,), layers - 1, np.int32),
            ),
        ),
        # q·Kᵀ + p·V over the live history columns plus the fresh column
        flops=4 * h * d * int(np.sum(fill + 1)),
        notes=f"{chunk} arena blocks a grid step, picked through the "
              "prefetched table; live blocks only are fetched",
    )


def _run_case(params):
    import numpy as np

    from nnstreamer_tpu.kv.block_attn import paged_attention_ref

    rng = np.random.default_rng(3)
    b, h, d, n_kv, bs, nb, n_blocks, dtype, _ = _case_geometry(params)
    layers = params.get("layers", 1)
    layer = layers - 1
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    tables = rng.permutation(n_blocks)[: b * nb].reshape(b, nb)
    # default fills spread slot positions from empty to full
    default_pos = [(i * nb * bs) // max(1, b - 1) for i in range(b)]
    pos = np.asarray(params.get("pos", default_pos), np.int32)
    fk = jnp.asarray(rng.standard_normal((b, 1, n_kv, d)), jnp.float32)
    fv = jnp.asarray(rng.standard_normal((b, 1, n_kv, d)), jnp.float32)
    shape = (layers, n_blocks, bs, n_kv, d)
    scales = {}
    if dtype == "int8":
        ak = rng.integers(-127, 128, shape).astype(np.int8)
        av = rng.integers(-127, 128, shape).astype(np.int8)
        scales = {
            nm: jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]), jnp.float32)
            for nm in ("k_scale", "v_scale")
        }
    else:
        ak = rng.standard_normal(shape).astype(np.float32)
        av = rng.standard_normal(shape).astype(np.float32)
        if params.get("poison"):
            # every position no slot fills (a finished request's stale
            # table among them, and the tail of a part-filled block)
            # holds NaN: one dead column let into the softmax would show
            dead = np.ones(shape[:3], bool)
            dead[:, 0] = False
            for row, p in zip(tables, pos):
                dead[:, row[: int(p) // bs]] = False
                if int(p) % bs:
                    dead[:, row[int(p) // bs], : int(p) % bs] = False
            ak[dead] = np.nan
            av[dead] = np.nan
    ak, av, tables, pos = map(jnp.asarray, (ak, av, tables, pos))
    got = paged_decode_attention(
        q, ak, av, tables, pos, fk, fv, layer=layer,
        chunk=params.get("chunk"), interpret=interpret_default(), **scales,
    )
    want = paged_attention_ref(
        q, ak[layer], av[layer], tables, pos, (fk, fv),
        **{nm: x[layer] for nm, x in scales.items()},
    )
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
        # the chunked grid: several blocks a grid step, two chunks and
        # a ragged last one, fills 0 / partial block / exact chunk edge
        # / full table, a finished lane (fill 0) whose stale table
        # points at poisoned blocks, the last layer of a whole arena
        _registry.ShapeCase(
            "chunked-fills-poisoned",
            {"b": 6, "h": 4, "bs": 8, "nb": 8, "n_blocks": 60, "chunk": 4,
             "pos": [0, 5, 32, 64, 37, 0], "poison": True, "layers": 3},
            tier1=True,
        ),
        _registry.ShapeCase(
            "chunked-gqa4-kv8-ragged",
            {"b": 3, "h": 32, "n_kv": 8, "bs": 8, "nb": 6, "n_blocks": 24,
             "chunk": 4, "pos": [48, 33, 7], "poison": True},
            tier1=True,
        ),
        _registry.ShapeCase(
            "chunked-int8",
            {"b": 3, "h": 2, "bs": 8, "nb": 5, "n_blocks": 16, "chunk": 2,
             "dtype": "int8", "pos": [0, 16, 40], "layers": 2},
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
        # the benchmark's cells (BENCHMARK.json): 16 slots x 64 table
        # entries over olmo-1b's arena, 32 x 64 over mistral-7b's
        _registry.ShapeCase(
            "cell-olmo-1b",
            {"b": 16, "h": 16, "d": 128, "bs": 16, "nb": 64,
             "n_blocks": 1025},
        ),
        _registry.ShapeCase(
            "cell-mistral-7b",
            {"b": 32, "h": 32, "n_kv": 8, "d": 128, "bs": 16, "nb": 64,
             "n_blocks": 2049},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
