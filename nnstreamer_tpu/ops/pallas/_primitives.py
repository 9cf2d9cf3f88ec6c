"""Shared tiling/DMA idioms of the in-tree Pallas kernels.

The three attention kernels (flash, decode, paged decode) are one
online-softmax recurrence specialized to different cache layouts; until
PR 19 each module carried its own copy of the init/update/finalize math.
This module is the single home (the first piece of ROADMAP item 5's
shared primitive layer): pure functions over values — the callers own
their scratch refs and write-back, so the kernels keep their exact
@pl.when predication structure.

Two forms of the same recurrence live here. The BLOCK form (flash) has
many query rows per head and runs both contractions on the MXU. The
DECODE form (decode, paged decode) has ONE query row per head and keeps
every head of a cache block in the block's own layout
``[n, KV, d]`` — heads on sublanes, head dim on lanes, exactly how the
serving caches sit in HBM — so a block is one contiguous DMA and no
per-head slice (which Mosaic's (8, 128) block rule refuses) is ever
taken: scores are a VPU multiply + lane reduce, the weighted sum a VPU
multiply + reduce over the leading (position) axis.

Numerics are the originals', bit-for-bit where it matters: f32
accumulation, the ``m <= NEG_INF`` guards that keep fully-masked
prefixes at weight exactly zero, and the ``l > 0`` guard that zeroes
rows nothing attended to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def scaled_qk(q, k, scale):
    """Scores block ``(q · kᵀ) * scale`` with f32 MXU accumulation.
    q [m, d], k [n, d] → [m, n] float32."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale


def online_softmax_init(m_ref, l_ref, acc_ref):
    """First-k-step scratch init: running max at NEG_INF (identity of
    max), denominator and accumulator at zero."""
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def online_softmax_update(s, v, m_prev, l_prev, acc_prev, p_dtype=None):
    """One block of the online-softmax recurrence.

    s [m, n] f32 scores, v [n, d] f32 values; (m_prev [m], l_prev [m],
    acc_prev [m, d]) the running (max, denominator, accumulator) →
    the updated triple. The ``<= NEG_INF`` guards pin fully-masked
    prefixes to weight exactly zero (exp(NEG_INF - NEG_INF) would be 1).
    ``p_dtype`` rounds the weights to the values' storage dtype for the
    p·V contraction (a bfloat16 cache: one MXU pass, float32 accumulation)."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    m2 = m_new[:, None]
    p = jnp.where(m2 <= NEG_INF, 0.0, jnp.exp(s - m2))
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
        p if p_dtype is None else p.astype(p_dtype), v,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def online_softmax_finalize(l, acc, dtype):
    """Normalize the accumulator by the denominator; rows nothing
    attended to (l == 0) come out exactly zero instead of 0/0."""
    l2 = l[:, None]
    return jnp.where(l2 > 0, acc / jnp.maximum(l2, 1e-30), 0.0).astype(dtype)


# -- decode form: one query row per head, all heads of a block at once ------


def load_cache_block(block_ref, scale_ref=None):
    """One cache block ``[n, KV, d]`` as float32; with ``scale_ref``
    (int8 caches) the per-token-per-head scales ``[n, KV]`` dequantize
    it in VMEM — HBM traffic stays at the quantized byte count, the
    point of a quantized cache."""
    x = block_ref[0].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0][:, :, None]
    return x


def decode_scores(q, k, scale):
    """Scores of one query row per head against a cache block:
    q [KV, d], k [n, KV, d] → [n, KV, 1] float32 (``(q · k) * scale``
    per position and head)."""
    return jnp.sum(k * q[None], axis=-1, keepdims=True) * scale


def decode_attend_block(q_ref, k, v, k_start, live_len, scale,
                        m_ref, l_ref, acc_ref):
    """Fold one cache block into the running softmax of every query
    group member: q_ref [1, g, KV, d]; k, v [n, KV, d] float32 holding
    positions ``k_start + i``; scratch m/l [g, KV, 1], acc [g, KV, d].

    Positions at/past ``live_len`` are masked to NEG_INF and their V
    rows zeroed: a dead position gets softmax weight exp(NEG_INF - m)
    = 0, but a pad/scratch block may hold arbitrary V bytes and
    0 * NaN = NaN — zeroing keeps the weighted sum clean."""
    n, n_kv, _ = k.shape
    live = (
        k_start + jax.lax.broadcasted_iota(jnp.int32, (n, n_kv, 1), 0)
        < live_len
    )
    v = jnp.where(live, v, 0.0)
    for gi in range(q_ref.shape[1]):
        s = decode_scores(q_ref[0, gi].astype(jnp.float32), k, scale)
        m_ref[gi], l_ref[gi], acc_ref[gi] = decode_softmax_update(
            jnp.where(live, s, NEG_INF), v,
            m_ref[gi], l_ref[gi], acc_ref[gi],
        )


def decode_softmax_update(s, v, m_prev, l_prev, acc_prev):
    """One cache block of the online-softmax recurrence, decode form.

    s [n, KV, 1] f32 scores, v [n, KV, d] f32 values; (m_prev [KV, 1],
    l_prev [KV, 1], acc_prev [KV, d]) → the updated triple. Same guards
    as :func:`online_softmax_update`."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    p = jnp.where(m_new[None] <= NEG_INF, 0.0, jnp.exp(s - m_new[None]))
    l_new = l_prev * alpha + jnp.sum(p, axis=0)
    acc_new = acc_prev * alpha + jnp.sum(p * v, axis=0)
    return m_new, l_new, acc_new


def decode_softmax_finalize(l, acc, dtype):
    """Decode-form normalize: l [KV, 1], acc [KV, d]; heads nothing
    attended to (l == 0) come out exactly zero instead of 0/0."""
    return jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0).astype(dtype)
