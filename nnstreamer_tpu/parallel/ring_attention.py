"""Ring attention: sequence-parallel exact attention over a mesh axis.

A genuinely new capability vs the reference (SURVEY.md §5.7: its "sequence"
axis is time; it has no attention). Long-context streams need attention over
sequences larger than one chip's HBM, so sequence parallelism is first-class
here: Q/K/V are sharded along the sequence dim over a mesh axis, K/V blocks
rotate around the ring via ``jax.lax.ppermute`` (ICI neighbor exchange —
the collective rides the torus links), and each device accumulates its
queries' attention with the flash-attention online-softmax recurrence, so
the full [T, T] score matrix never materializes (Liu et al. 2023,
arXiv:2310.01889 pattern; implementation is original).

The ring loop is a ``lax.scan`` (reverse-differentiable: ppermute has a
transpose rule, so the same code path trains). Causal masking uses global
block offsets from ``axis_index``; fully-masked blocks contribute zeros
(compute is not skipped — at ring scale the skip is a constant factor the
overlap hides).

Layouts: q, k, v are [batch, seq_local, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG_INF = -1e30


def _online_block(q, k, v, mask, m_prev, l_prev, o_prev, scale):
    """One flash-attention accumulation step over a K/V block.

    q [B,Tq,H,D], k/v [B,Tk,H,D], mask [Tq,Tk] True=attend.
    Running stats: m (max) [B,H,Tq], l (denominator) [B,H,Tq],
    o (unnormalized out) [B,Tq,H,D].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    # guard exp(-inf - -inf): a still-empty row keeps alpha = 0
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    p = jnp.where(
        (m_new <= NEG_INF)[..., None], 0.0, jnp.exp(s - m_new[..., None])
    )
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    o_new = o_prev * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v
    )
    return m_new, l_new, o_new


def _online_block_chunked(q, k, v, mask, m_prev, l_prev, o_prev, scale,
                          chunk: int):
    """Same recurrence, but scanning K/V in ``chunk``-sized pieces so the
    live score tensor is [B,H,Tq,chunk] instead of [B,H,Tq,Tk] — the
    HBM-bounding path for long local sequences (the in-shard analogue of
    the ring's cross-shard blocking)."""
    tk = k.shape[1]
    if chunk <= 0 or tk % chunk:
        raise ValueError(
            f"ring attention: kv_chunk must be a positive divisor of the "
            f"local sequence ({tk}), got {chunk}"
        )
    nc = tk // chunk
    b, _, h, d = k.shape
    kc = k.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)
    maskc = mask.reshape(mask.shape[0], nc, chunk).transpose(1, 0, 2)

    def step(carry, xs):
        m, l, o = carry
        kb, vb, mb = xs
        m, l, o = _online_block(q, kb, vb, mb, m, l, o, scale)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        step, (m_prev, l_prev, o_prev), (kc, vc, maskc)
    )
    return m, l, o


def ring_attention_local(
    q, k, v, axis_name: str, causal: bool = True,
    scale: Optional[float] = None, kv_chunk: Optional[int] = None,
):
    """The per-shard computation (call inside shard_map / shard-mapped jit).

    Sequence is sharded contiguously over ``axis_name``: shard i holds
    global positions [i*Tl, (i+1)*Tl). Returns the local output block
    [B, Tl, H, D] in float32. ``kv_chunk`` bounds the live score tensor to
    [B,H,Tl,kv_chunk] (long-context HBM control); None = whole block.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)

    q_pos = my * tl + jnp.arange(tl)  # global positions of local queries

    m0 = jnp.full((b, h, tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tl), jnp.float32)
    o0 = jnp.zeros((b, tl, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        kb, vb, m, l, o = carry
        # after i rotations we hold the block originally on shard (my - i)
        src = (my - i) % n
        k_pos = src * tl + jnp.arange(tl)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((tl, tl), bool)
        kf, vf = kb.astype(jnp.float32), vb.astype(jnp.float32)
        if kv_chunk is not None and kv_chunk < tl:
            m, l, o = _online_block_chunked(
                qf, kf, vf, mask, m, l, o, scale, kv_chunk
            )
        else:
            m, l, o = _online_block(qf, kf, vf, mask, m, l, o, scale)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (kb, vb, m, l, o), None

    (_, _, m, l, o), _ = jax.lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n)
    )
    denom = l.transpose(0, 2, 1)[..., None]  # [B,Tq,H,1]
    return jnp.where(denom > 0, o / jnp.maximum(denom, 1e-30), 0.0)


def make_ring_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = True,
    kv_chunk: Optional[int] = None,
):
    """Jitted full-array entry: (q, k, v) [B, T, H, D] sequence-sharded over
    ``axis`` → attention output with the same sharding."""
    spec = P(None, axis, None, None)

    fn = jax.shard_map(
        functools.partial(
            ring_attention_local, axis_name=axis, causal=causal,
            kv_chunk=kv_chunk,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)


def dense_attention(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Single-device reference (and the small-sequence fast path)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
