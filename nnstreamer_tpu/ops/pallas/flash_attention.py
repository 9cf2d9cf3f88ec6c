"""Flash attention as a Pallas TPU kernel.

The single-chip hot path for the long-context family (models/transformer.py)
— the [T, T] score matrix never leaves VMEM: the grid walks (batch*heads,
q-blocks, k-blocks) with the k dimension innermost ("arbitrary" semantics —
sequential on TPU), carrying the online-softmax running max/denominator/
accumulator in VMEM scratch across k iterations. Q/K/V blocks stream
HBM→VMEM via BlockSpecs (double-buffered by the pallas pipeline); the
s = q·kᵀ and p·v contractions hit the MXU with float32 accumulation
(preferred_element_type), so bfloat16 inputs keep full softmax precision.

Causal masking compares global row/col indices built from program_id;
fully-masked k-blocks are predicated off with @pl.when, so the causal case
does ~half the work. Matches parallel/ring_attention.dense_attention to
float tolerance (tests/test_pallas.py); composes with ring attention by
serving as the per-shard block math (the same online recurrence
ring_attention_local runs per rotation).

The online-softmax recurrence itself lives in ops/pallas/_primitives.py
(shared with the decode and paged-decode kernels); this module owns the
causal/pad masking and the [B, T, H, D] blocking, and registers the
whole launch geometry with ops/pallas/registry.py for nns-kscope.

Layout: [B, T, H, D] like the rest of the framework; internally [B*H, T, D].
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
    online_softmax_init,
    online_softmax_update,
    scaled_qk,
)


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_k: int,
    valid_len: Optional[int],
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # predicate off blocks with no live entries: strictly-above-diagonal
    # (causal) and fully-padded (valid_len) ones
    live = True
    if causal:
        live = q_start + block_q - 1 >= k_start
    if valid_len is not None:
        live = jnp.logical_and(live, k_start < valid_len)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = scaled_qk(q, k, scale)  # [bq, bk]
        if causal or valid_len is not None:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = jnp.ones(s.shape, bool)
            if causal:
                mask = rows >= cols
            if valid_len is not None:
                mask = jnp.logical_and(mask, cols < valid_len)
            s = jnp.where(mask, s, NEG_INF)
        m_ref[:], l_ref[:], acc_ref[:] = online_softmax_update(
            s, v, m_ref[:], l_ref[:], acc_ref[:]
        )

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[0] = online_softmax_finalize(l_ref[:], acc_ref[:], o_ref.dtype)


# BlockSpec index maps — module-level so the registered LaunchPlan and
# the live pallas_call share the SAME callables (grid (b*h, q, k))
def _q_index_map(i, j, kk):
    return (i, j, 0)


def _kv_index_map(i, j, kk):
    return (i, kk, 0)


def _blocking(t: int, block_q: int, block_k: int):
    """(bq, bk, t_pad, n_q, n_k): T pads up to a block multiple; tiny
    sequences shrink the block (16 floor keeps a sublane-full tile)."""
    bq = min(block_q, max(t, 16))
    bk = min(block_k, max(t, 16))
    blk = max(bq, bk)
    t_pad = -(-t // blk) * blk
    return bq, bk, t_pad, t_pad // bq, t_pad // bk


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """q, k, v: [B, T, H, D] → [B, T, H, D] float32.

    T pads up to a block multiple internally; padded key columns are
    masked to NEG_INF and padded query rows are sliced off on return."""
    b, t, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, bk, t_pad, n_q, n_k = _blocking(t, block_q, block_k)

    def to_bh(x):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        if t_pad != t:
            x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
        return x

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        block_q=bq,
        block_k=bk,
        n_k=n_k,
        valid_len=t if t_pad != t else None,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, t_pad, d), jnp.float32),
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), _q_index_map),
            pl.BlockSpec((1, bk, d), _kv_index_map),
            pl.BlockSpec((1, bk, d), _kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), _q_index_map),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qb, kb, vb)
    return out[:, :t, :].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def make_flash_attention(interpret: Optional[bool] = None, **kwargs):
    """attn_fn factory matching the transformer's pluggable signature.
    interpret=None auto-selects: real kernel on TPU, interpreter
    elsewhere. Each trace consults the registry's dtype support
    (_compat.pallas_ok) and degrades to the dense jnp reference with a
    logged reason instead of a trace-time Mosaic error; the resolved
    choice lands in the dispatch tally as op "flash_attention"."""
    from nnstreamer_tpu.ops.dispatch import record as _record_dispatch
    from nnstreamer_tpu.ops.pallas._compat import pallas_ok

    if interpret is None:
        interpret = interpret_default()

    def attn(q, k, v, causal: bool = True):
        ok, _ = pallas_ok("flash_attention", q.dtype)
        _record_dispatch("flash_attention", "pallas" if ok else "jnp")
        if not ok:
            from nnstreamer_tpu.parallel.ring_attention import dense_attention

            return dense_attention(q, k, v, causal=causal)
        return flash_attention(q, k, v, causal=causal, interpret=interpret, **kwargs)

    return attn


# -- kernel registration (nns-kscope) ----------------------------------------


def _plan(params):
    b = params.get("b", 1)
    t = params["t"]
    h = params.get("h", 2)
    d = params.get("d", 64)
    dtype = params.get("dtype", "float32")
    causal = params.get("causal", True)
    bq, bk, t_pad, n_q, n_k = _blocking(
        t, params.get("block_q", 128), params.get("block_k", 128)
    )
    arr = (b * h, t_pad, d)
    # two MXU contractions (q·kᵀ, p·v), 2·m·n·k flops each; causal
    # predication skips the strictly-above-diagonal half
    flops = 4 * b * h * t_pad * t_pad * d
    if causal:
        flops //= 2
    return _registry.LaunchPlan(
        grid=(b * h, n_q, n_k),
        blocks=(
            _registry.BlockDesc("q", "in", arr, (1, bq, d), dtype, _q_index_map),
            _registry.BlockDesc("k", "in", arr, (1, bk, d), dtype, _kv_index_map),
            _registry.BlockDesc("v", "in", arr, (1, bk, d), dtype, _kv_index_map),
            _registry.BlockDesc("o", "out", arr, (1, bq, d), "float32", _q_index_map),
        ),
        scratch=(
            _registry.ScratchDesc("m", (bq,)),
            _registry.ScratchDesc("l", (bq,)),
            _registry.ScratchDesc("acc", (bq, d)),
        ),
        flops=flops,
        notes="causal: ~half the k blocks predicated off" if causal else "",
    )


def _run_case(params):
    import numpy as np

    from nnstreamer_tpu.parallel.ring_attention import dense_attention

    rng = np.random.default_rng(0)
    b, t = params.get("b", 1), params["t"]
    h, d = params.get("h", 2), params.get("d", 64)
    dtype = jnp.dtype(params.get("dtype", "float32"))
    causal = params.get("causal", True)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32).astype(dtype)
        for _ in range(3)
    )
    got = flash_attention(
        q, k, v, causal=causal,
        block_q=params.get("block_q", 128),
        block_k=params.get("block_k", 128),
        interpret=interpret_default(),
    )
    want = dense_attention(q, k, v, causal=causal)
    return got, want, (2e-2 if dtype == jnp.bfloat16 else 2e-5)


def _probe():
    import numpy as np

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 16, 1, 8)), jnp.float32)
        for _ in range(3)
    )
    np.asarray(make_flash_attention(block_q=16, block_k=16)(q, k, v))


_registry.register(_registry.KernelSpec(
    name="flash_attention",
    module=__name__,
    ops=("flash_attention",),
    dtypes=("float32", "bfloat16"),
    cases=(
        _registry.ShapeCase(
            "t64-causal",
            {"b": 2, "t": 64, "h": 4, "d": 16, "block_q": 16, "block_k": 16},
            tier1=True,
        ),
        _registry.ShapeCase(
            "t64-full",
            {"b": 2, "t": 64, "h": 4, "d": 16, "block_q": 16, "block_k": 16,
             "causal": False},
        ),
        _registry.ShapeCase(
            "t100-pad-causal",
            {"b": 2, "t": 100, "h": 2, "d": 32, "block_q": 32, "block_k": 32},
            tier1=True,
        ),
        _registry.ShapeCase(
            "t100-pad-full",
            {"b": 2, "t": 100, "h": 2, "d": 32, "block_q": 32, "block_k": 32,
             "causal": False},
        ),
        _registry.ShapeCase(
            "bf16",
            {"b": 2, "t": 64, "h": 4, "d": 16, "block_q": 16, "block_k": 16,
             "dtype": "bfloat16"},
            tier1=True,
        ),
        _registry.ShapeCase(
            "serve-512", {"b": 8, "t": 512, "h": 8, "d": 128},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
