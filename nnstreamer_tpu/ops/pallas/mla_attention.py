"""Absorbed latent (MLA) decode attention over the paged latent arena, as a
Pallas TPU kernel: one shared 576-wide "KV head" against every query head,
both contractions on the matrix unit, live blocks only.

The LongCat-Flash family (models/longcat.py) caches, per token and attention
sublayer, the latent ``c`` (``kv_rank`` values) and the rotated shared key
``k_r`` (padded to whole lanes): two arena leaves ``[2L, N, bs, kv_rank]`` and
``[2L, N, bs, W]``. Decode reads them in the absorbed form: the query arrives
already in the latent's space (``q' = q_nope W_kvb,k^T``), scores are
``q'.c + q_rope.k_r``, and the output is ``sum p c`` (``W_kvb,v`` and ``W_o``
follow outside). That is H = 64 query rows against each cached token: 139
kFLOP a token and attention, 121 FLOP a cached byte — a matrix-unit
contraction, where the dense family's kernel (ops/pallas/paged_attention.py:
1-4 query rows a KV head, folded on the vector unit) would be the whole step.

Mechanics: grid ``(B,)``, one program a slot. The block tables and fill
levels ride as scalar-prefetch operands; both arena leaves stay in HBM
(``pl.ANY``), passed WHOLE (the sublayer index is static). A slot's program
walks ``ceil(fill / (C * bs))`` chunks of C live blocks: one ``[bs, width]``
DMA per live block and leaf into a double-buffered VMEM chunk (the next
chunk's copies are in flight while this one is folded), then
``[H, kv_rank] x [kv_rank, C*bs]`` + ``[H, W] x [W, C*bs]`` for the scores,
the shared online-softmax update (ops/pallas/_primitives.py) and
``[H, C*bs] x [C*bs, kv_rank]`` for the weighted sum. A dead table entry is
never read, a dead slot (fill 0) reads nothing; the dead tail of the last
chunk is masked to weight exactly zero and its rows zeroed.

The pending token's own latent is not in the arena yet: the kernel returns the
history's normalised output with its running max and denominator, and the
caller folds the pending column in (one more online-softmax step), as the XLA
formulation does.
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
    NEG_INF,
    online_softmax_finalize,
    online_softmax_update,
    scaled_qk,
)

CHUNK_TOKENS = 512   # positions folded per matrix-unit pass
LANES = 128


def _kernel(tab_ref, fill_ref, ql_ref, qr_ref, lat_hbm, kr_hbm,
            o_ref, m_ref, l_ref, lat_buf, kr_buf, sem, *,
            layer: int, scale: float, bs: int, chunk: int, nb: int):
    b = pl.program_id(0)
    fill = fill_ref[b]
    span = chunk * bs
    n_chunks = (fill + span - 1) // span
    n_blocks = (fill + bs - 1) // bs

    def copies(c, slot):
        """(logical block, its two DMAs) for every block of chunk ``c``."""
        for i in range(chunk):
            j = c * chunk + i
            phys = tab_ref[b, jnp.minimum(j, nb - 1)]
            rows = pl.ds(i * bs, bs)
            yield j, (
                pltpu.make_async_copy(lat_hbm.at[layer, phys],
                                      lat_buf.at[slot, rows], sem.at[0, slot]),
                pltpu.make_async_copy(kr_hbm.at[layer, phys],
                                      kr_buf.at[slot, rows], sem.at[1, slot]),
            )

    def start(c, slot):
        for j, dmas in copies(c, slot):
            @pl.when(j < n_blocks)
            def _():
                for dma in dmas:
                    dma.start()

    def wait(c, slot):
        for j, dmas in copies(c, slot):
            @pl.when(j < n_blocks)
            def _():
                for dma in dmas:
                    dma.wait()

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    q_lat = ql_ref[0]
    q_rope = qr_ref[0]
    h, r = q_lat.shape

    def fold(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        first = c * span
        live_rows = first + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0) < fill
        live_cols = first + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) < fill
        # dead rows may hold anything (the last chunk's stale tail): zero
        # them, so that weight 0 x NaN cannot reach the sum
        lat = jnp.where(live_rows, lat_buf[slot], 0).astype(lat_buf.dtype)
        kr = kr_buf[slot]
        s = scaled_qk(q_lat, lat, scale) + scaled_qk(q_rope, kr, scale)
        s = jnp.where(live_cols, s, NEG_INF)
        return online_softmax_update(s, lat, m, l, acc, p_dtype=lat.dtype)

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, fold,
        (jnp.full((h,), NEG_INF, jnp.float32), jnp.zeros((h,), jnp.float32),
         jnp.zeros((h, r), jnp.float32)))
    o_ref[0] = online_softmax_finalize(l, acc, jnp.float32)
    m_ref[0] = jnp.broadcast_to(m[:, None], (h, LANES))
    l_ref[0] = jnp.broadcast_to(l[:, None], (h, LANES))


def _row_index_map(i, *_):
    """One slot's rows: the query, output and (m, l) blocks of grid step i."""
    return (i, 0, 0)


def blocks_per_chunk(bs: int, nb: int) -> int:
    return max(1, min(nb, CHUNK_TOKENS // bs))


@functools.partial(jax.jit, static_argnames=("layer", "scale", "chunk", "interpret"))
def mla_paged_decode_attention(q_lat, q_rope, lat_arena, kr_arena, tables, fill,
                               *, layer: int, scale: float,
                               chunk: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """q_lat [B, H, R], q_rope [B, H, W] in the arena's dtype; lat_arena
    [2L, N, bs, R], kr_arena [2L, N, bs, W]; tables [B, nb] int32; fill [B]
    int32 (history length; 0 on a dead slot) -> (o [B, H, R] float32: the
    softmax-weighted sum of the history's latents, normalised; m, l [B, H]
    float32: its running max (``NEG_INF`` where nothing is live) and
    denominator)."""
    b, h, r = q_lat.shape
    w = q_rope.shape[-1]
    bs = lat_arena.shape[2]
    nb = tables.shape[1]
    chunk = chunk or blocks_per_chunk(bs, nb)
    if interpret is None:
        interpret = interpret_default()
    kernel = functools.partial(_kernel, layer=layer, scale=scale, bs=bs,
                               chunk=chunk, nb=nb)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, r), _row_index_map),
                pl.BlockSpec((1, h, w), _row_index_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, h, r), _row_index_map),
                pl.BlockSpec((1, h, LANES), _row_index_map),
                pl.BlockSpec((1, h, LANES), _row_index_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, chunk * bs, r), lat_arena.dtype),
                pltpu.VMEM((2, chunk * bs, w), kr_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, r), jnp.float32),
            jax.ShapeDtypeStruct((b, h, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_decode_attention",
    )(tables.astype(jnp.int32), fill.astype(jnp.int32), q_lat, q_rope,
      lat_arena, kr_arena)
    return o, m[..., 0], l[..., 0]


def mla_paged_attention_ref(q_lat, q_rope, lat_arena, kr_arena, tables, fill,
                            *, layer: int, scale: float):
    """The XLA formulation the kernel is pinned against (and the off-TPU
    default): each slot's view taken through the tables, dead columns masked."""
    bs = lat_arena.shape[2]
    b, nb = tables.shape
    dt = lat_arena.dtype
    view_l = lat_arena[layer, tables].reshape(b, nb * bs, -1)
    view_r = kr_arena[layer, tables].reshape(b, nb * bs, -1)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(dt), view_l,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope.astype(dt), view_r,
                      preferred_element_type=jnp.float32)) * scale
    hist = jnp.arange(nb * bs)[None, None, :] < fill[:, None, None]
    s = jnp.where(hist, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(hist, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    view_l = jnp.where((jnp.arange(nb * bs)[None, :] < fill[:, None])[..., None],
                       view_l, 0)
    o = jnp.einsum("bhs,bsr->bhr", p.astype(dt), view_l,
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(l, 1e-30)[..., None], m, l


# -- registry (nns-kscope) ---------------------------------------------------


def _case_geometry(params):
    return (params.get("b", 2), params.get("h", 4), params.get("r", 128),
            params.get("w", 128), params.get("bs", 8), params.get("nb", 4),
            params.get("n_blocks", 12), params.get("dtype", "float32"),
            params.get("layers", 2))


def _plan(params):
    import numpy as np

    b, h, r, w, bs, nb, n_blocks, dtype, layers = _case_geometry(params)
    chunk = params.get("chunk") or blocks_per_chunk(bs, nb)
    fill = np.clip(np.asarray(params.get("fill", [nb * bs] * b), np.int64), 0, nb * bs)
    blocks = (
        _registry.BlockDesc("q_lat", "in", (b, h, r), (1, h, r), dtype, _row_index_map),
        _registry.BlockDesc("q_rope", "in", (b, h, w), (1, h, w), dtype, _row_index_map),
        _registry.BlockDesc("o", "out", (b, h, r), (1, h, r), "float32", _row_index_map),
        _registry.BlockDesc("m", "out", (b, h, LANES), (1, h, LANES), "float32",
                            _row_index_map),
        _registry.BlockDesc("l", "out", (b, h, LANES), (1, h, LANES), "float32",
                            _row_index_map),
    )
    return _registry.LaunchPlan(
        grid=(b,),
        blocks=blocks,
        scratch=(
            _registry.ScratchDesc("lat_chunks", (2, chunk * bs, r), dtype),
            _registry.ScratchDesc("kr_chunks", (2, chunk * bs, w), dtype),
        ),
        prefetch=(
            _registry.PrefetchDesc(
                "tables", (b, nb),
                make=lambda: np.arange(b * nb, dtype=np.int32).reshape(b, nb) % n_blocks),
            _registry.PrefetchDesc("fill", (b,), make=lambda: fill.astype(np.int32)),
        ),
        # q'.c + q_rope.k_r and p.c over the live history columns
        flops=2 * h * (2 * r + w) * int(np.sum(fill)),
        notes=f"arena leaves stay in HBM; {chunk} live blocks a chunk, one DMA "
              "a block and leaf, double-buffered",
    )


def _run_case(params):
    import numpy as np

    rng = np.random.default_rng(5)
    b, h, r, w, bs, nb, n_blocks, dtype, layers = _case_geometry(params)
    layer = layers - 1
    dt = jnp.dtype(dtype)
    default_fill = [(i * nb * bs) // max(1, b - 1) for i in range(b)]
    fill = np.asarray(params.get("fill", default_fill), np.int32)
    tables = 1 + rng.permutation(n_blocks - 1)[: b * nb].reshape(b, nb)
    lat = rng.standard_normal((layers, n_blocks, bs, r)).astype(np.float32)
    kr = rng.standard_normal((layers, n_blocks, bs, w)).astype(np.float32)
    if params.get("poison"):
        dead = np.ones((layers, n_blocks, bs), bool)
        for row, p in zip(tables, fill):
            dead[:, row[: int(p) // bs]] = False
            if int(p) % bs:
                dead[:, row[int(p) // bs], : int(p) % bs] = False
        lat[dead] = np.nan
        kr[dead] = np.nan
    q_lat = jnp.asarray(rng.standard_normal((b, h, r)), dt)
    q_rope = jnp.asarray(rng.standard_normal((b, h, w)), dt)
    args = (q_lat, q_rope, jnp.asarray(lat, dt), jnp.asarray(kr, dt),
            jnp.asarray(tables, jnp.int32), jnp.asarray(fill))
    kw = dict(layer=layer, scale=1.0 / (r + w) ** 0.5)
    got = mla_paged_decode_attention(*args, chunk=params.get("chunk"),
                                     interpret=interpret_default(), **kw)
    want = mla_paged_attention_ref(*args, **kw)
    pack = lambda o, m, l: jnp.concatenate(  # noqa: E731
        [o, jnp.where(l > 0, m, 0.0)[..., None], l[..., None]], axis=-1)
    return pack(*got), pack(*want), 2e-5 if dtype == "float32" else 3e-2


def _probe():
    from nnstreamer_tpu.ops.dispatch import record

    record("mla_attention", "pallas")
    _run_case({"b": 1, "h": 2, "bs": 8, "nb": 2, "n_blocks": 4, "fill": [11]})


_registry.register(_registry.KernelSpec(
    name="mla_paged_decode_attention",
    module=__name__,
    ops=("mla_attention",),
    dtypes=("float32", "bfloat16"),
    cases=(
        # ragged fills: empty, inside a block, a chunk's edge, the full table;
        # a dead slot whose stale table points at poisoned blocks; the last
        # sublayer of a whole arena
        _registry.ShapeCase(
            "ragged-fills-poisoned",
            {"b": 6, "h": 4, "bs": 8, "nb": 8, "n_blocks": 60, "chunk": 2,
             "fill": [0, 5, 32, 64, 37, 0], "poison": True, "layers": 3},
            tier1=True,
        ),
        _registry.ShapeCase(
            "one-chunk-bf16",
            {"b": 3, "h": 8, "bs": 16, "nb": 4, "n_blocks": 16, "dtype": "bfloat16",
             "fill": [64, 17, 1]},
            tier1=True,
        ),
        # the benchmark's cell: 64 heads, 512 + 128 lanes, 64 slots x 128 entries
        _registry.ShapeCase(
            "cell-longcat-flash",
            {"b": 64, "h": 64, "r": 512, "w": 128, "bs": 16, "nb": 128,
             "n_blocks": 8193, "dtype": "bfloat16", "layers": 8},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
