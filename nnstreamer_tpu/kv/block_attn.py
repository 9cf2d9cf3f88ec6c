"""Block-native paged attention: decode/verify straight off the arena.

The paged step never holds a contiguous ``[L, B, max_len, ...]`` view of
the cache: every decode pump would carry it BESIDE the arena (a transient
HBM doubling) and scatter it back — the intermediate materialization a
streaming dataflow must not pay (StreamTensor, PAPERS.md). The slot layout
(``ContinuousBatcher(kv_layout="slot")``) is the oracle: block-native
streams are bitwise identical to it, pinned by tests/test_kv_block_attn.py
and tests/test_kv_paged.py.

- the attention READ takes each layer's blocks through the block table
  *inside* that layer's body (:func:`_take_layer`, one per-layer
  transient instead of an L-deep carried view) and runs the IDENTICAL
  masked-softmax expressions the slot step runs on its cache;
- the WRITE is :func:`write_fresh_window`: the freshly computed K/V of
  the pending token (or verify chunk) lands in its owning arena
  block(s) with ONE scatter per leaf on the donated arena. Inactive
  lanes route to scratch block 0 carrying its init values (zero
  payload, unit scales), so scratch stays pristine and shared /
  copy-on-write blocks are never touched: the write window lies in
  blocks the request owns privately (the pool's CoW discipline);
- :func:`paged_attention_ref` is the per-block ONLINE-softmax jnp
  reference of the Pallas block-table kernel
  (:mod:`nnstreamer_tpu.ops.pallas.paged_attention`): one take per
  logical block, the flash recurrence across blocks, scratch and
  beyond-fill columns masked to exact zeros, the pending token's own
  column folded last (it is the highest live position, so the
  reduction order matches position order). :func:`block_attention`
  dispatches ``impl="auto"|"jnp"|"pallas"`` like the PR-12 kernels —
  the kernel on a real TPU backend, the reference elsewhere.

The admission-path ops (``write_block`` / ``read_block`` /
``copy_block`` and chunked-prefill staging) and the int8 entry are in
:mod:`nnstreamer_tpu.kv.gather`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from nnstreamer_tpu.kv.gather import dequantize_kv, quantize_kv
from nnstreamer_tpu.models import transformer as tfm

NEG_INF = -1e30


def _write_view(c, new, pos, gate):
    """[B, w, ...] chunk into the per-slot view at per-slot ``pos``,
    gated on active — the EXACT write expression of the slot layout's
    step/verify bodies (the bitwise-parity pin rides on this); the
    decode step is just the w=1 case."""
    written = jax.vmap(
        lambda cb, nb, p: jax.lax.dynamic_update_slice(cb, nb, (p, 0, 0))
    )(c, new.astype(c.dtype), pos)
    return jnp.where(gate, written, c)


def _write_view_scale(sc, new, pos, gate):
    written = jax.vmap(
        lambda sb, nb, p: jax.lax.dynamic_update_slice(sb, nb, (p, 0))
    )(sc, new, pos)
    return jnp.where(gate[..., 0], written, sc)


def _take_layer(layer, tables):
    """One layer's arena leaf ``[N, bs, ...]`` → the contiguous per-slot
    view ``[B, nb*bs, ...]`` through ``tables`` [B, nb], materialized
    transiently inside the layer body instead of carried (and scattered
    back) across the whole program."""
    b, nb = tables.shape
    t = jnp.take(layer, tables, axis=0)  # [B, nb, bs, ...]
    return t.reshape((b, nb * layer.shape[1]) + layer.shape[2:])


def write_fresh_window(arena, tables, fresh, pos, width: int, active,
                       quantized: bool, per_layer: bool = False):
    """Land freshly computed K/V straight into its owning arena blocks.

    ``fresh`` holds the per-layer stacked chunk values —
    ``(k, v)`` [L, B, width, KV, Dh] (fp) or ``(k8, ks, v8, vs)``
    (int8 payloads + [L, B, width, KV] scales) — exactly what the layer
    bodies computed and wrote into their attention views. Token column
    ``c`` of lane ``b`` goes to arena block ``tables[b, (pos+c)//bs]``
    at row ``(pos+c) % bs``: ONE scatter per arena leaf, in place under
    donation. Inactive lanes (and out-of-range columns) are routed to
    scratch block 0 and write its init values (zero payload, unit
    scales), so scratch stays pristine; active lanes' windows lie in
    privately-owned blocks (copy-on-write discipline), so shared blocks
    are untouched by construction.

    ``per_layer`` lands each cache layer with a scatter of its own. A
    program that READS single layers of a whole leaf (``leaf[li, tables]``,
    the latent family's unrolled step) needs it: one scatter across the
    layer dim made the TPU compiler carry the arena layer-minor through
    the pump's loop and copy the whole leaf back to layer-major at every
    step (3.2 + 0.8 ms a step on a 1.3 GB arena, PERF.md section 6, PR 27)."""
    first = arena[0][0] if quantized else arena[0]
    bs = first.shape[2]
    nb = tables.shape[1]
    p = pos[:, None] + jnp.arange(int(width), dtype=jnp.int32)[None, :]
    lb = p // bs                                    # [B, w] logical block
    off = (p % bs).reshape(-1)
    valid = active[:, None] & (lb < nb)
    phys = jnp.take_along_axis(tables, jnp.clip(lb, 0, nb - 1), axis=1)
    phys = jnp.where(valid, phys, 0).reshape(-1)
    valid = valid.reshape(-1)

    def put(a, rows, fill=0):
        # rows [L, B, w, ...] → [L, B*w, ...]; duplicate targets exist
        # only among routed-to-scratch lanes, and they all write the
        # identical fill value — deterministic whatever the scatter order
        rows = rows.reshape((rows.shape[0], -1) + rows.shape[3:])
        keep = valid.reshape((1, -1) + (1,) * (rows.ndim - 2))
        rows = jnp.where(keep, rows.astype(a.dtype),
                         jnp.asarray(fill, a.dtype))
        if per_layer:
            for li in range(a.shape[0]):
                a = a.at[li, phys, off].set(rows[li])
            return a
        return a.at[:, phys, off].set(rows)

    if quantized:
        k8, ks, v8, vs = fresh
        (ka, ksc), (va, vsc) = arena
        return (
            (put(ka, k8), put(ksc, ks, 1.0)),
            (put(va, v8), put(vsc, vs, 1.0)),
        )
    k, v = fresh
    ka, va = arena
    return (put(ka, k), put(va, v))


def batched_decode_step_block(
    params,
    tok,
    pos,
    active,
    arena,
    tables,
    n_heads: int,
    compute_dtype=jnp.float32,
    attn_fn=None,
):
    """One decode step for a slot batch, directly against the block
    arena — the block-native sibling of
    ``models/serving.batched_decode_step``.

    tok/pos/active [B] as in the slot step; ``arena`` is the kv.gather
    arena tree (leaves [L, N, bs, ...]), ``tables`` [B, nb] int32 →
    (logits [B, V] f32, arena', pos'). The arena write is deferred to
    one :func:`write_fresh_window` scatter after the layer scan (in
    place under donation; no carried view).

    The attention read has two formulations. Inline (``attn_fn`` None,
    the XLA oracle): per layer the view is taken through the tables and
    the pending token's K/V is written into it with the EXACT
    expressions of the slot step — bitwise parity with the slot layout
    by construction. With ``attn_fn(q, k_entry, v_entry, tables,
    fill, (fresh_k, fresh_v), layer=li) -> [B,1,H,Dh]`` (the block-table
    kernel, ops/pallas/paged_attention.py) no view exists at all: the
    layer scan carries only the layer INDEX and every layer reads the
    same WHOLE arena leaves through the tables, live blocks only
    (``fill`` is ``pos`` on active lanes and 0 elsewhere, so a finished
    lane's stale table is never walked) — nothing layer-sized is sliced
    or copied in front of the kernel."""
    quantized = isinstance(arena[0], tuple)
    first = arena[0][0] if quantized else arena[0]
    bs_blk = first.shape[2]
    max_len = tables.shape[1] * bs_blk
    x = tfm.embed_lookup(params["embed"], tok, compute_dtype)[:, None, :]
    gate = active[:, None, None, None]
    fill = jnp.where(active, pos, 0)

    def write(c, new):
        return _write_view(c, new, pos, gate)

    def write_scale(sc, new):
        return _write_view_scale(sc, new, pos, gate)

    def body(carry, layer):
        x = carry
        blk, *kv = layer
        bsz, _, d = x.shape
        with jax.named_scope("nns.attn"):
            q, k, v = tfm.block_qkv(x, blk, n_heads, pos[:, None])
            if quantized:
                k8, ks = quantize_kv(k)
                v8, vs = quantize_kv(v)
                fresh = (k8, ks, v8, vs)
            else:
                fresh = (k, v)
            if attn_fn is not None:
                (li,) = kv
                fresh_kv = (
                    (dequantize_kv(k8, ks), dequantize_kv(v8, vs))
                    if quantized else fresh
                )
                o = attn_fn(
                    q, arena[0], arena[1], tables, fill, fresh_kv, layer=li
                )
            else:
                if quantized:
                    ka, ksc, va, vsc = kv
                    ck = dequantize_kv(
                        write(_take_layer(ka, tables), k8),
                        write_scale(_take_layer(ksc, tables), ks),
                    )
                    cv = dequantize_kv(
                        write(_take_layer(va, tables), v8),
                        write_scale(_take_layer(vsc, tables), vs),
                    )
                else:
                    ka, va = kv
                    ck = write(_take_layer(ka, tables), k)
                    cv = write(_take_layer(va, tables), v)
                mask = jnp.arange(max_len)[None, :] <= pos[:, None]
                o = tfm.cache_attention(q, ck, cv, mask[:, None, :])
            o = o.astype(x.dtype).reshape(bsz, 1, -1)
            x = x + o @ tfm.wt(blk["wo"], x.dtype)
        with jax.named_scope("nns.ffn"):
            x = tfm.block_ffn(x, blk)
        return x, fresh

    if attn_fn is not None:
        xs = (params["blocks"], jnp.arange(first.shape[0], dtype=jnp.int32))
    elif quantized:
        (ka, ksc), (va, vsc) = arena
        xs = (params["blocks"], ka, ksc, va, vsc)
    else:
        xs = (params["blocks"],) + tuple(arena)
    x, fresh_layers = jax.lax.scan(body, x, xs)
    arena = write_fresh_window(
        arena, tables, fresh_layers, pos, 1, active, quantized
    )
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)[:, 0]
    return logits, arena, pos + active.astype(jnp.int32)


def batched_verify_step_block(
    params,
    toks,
    pos,
    active,
    arena,
    tables,
    n_heads: int,
    compute_dtype=jnp.float32,
):
    """Score per-slot k-token candidate chunks in one forward against
    the block arena — the block-native sibling of
    ``models/serving.batched_verify_step`` (same chunk-write-then-mask
    invariant: rejected positions are overwritten by a later round
    before any mask can reach them). toks [B, k] → (logits [B, k, V]
    f32, arena'). Attention reads ride the per-layer take; the chunk's
    K/V lands via one :func:`write_fresh_window` scatter (≤ k columns,
    each in its privately-owned block). Caller guarantees pos + k ≤
    max_len for active lanes, exactly as for the slot verify."""
    quantized = isinstance(arena[0], tuple)
    first = arena[0][0] if quantized else arena[0]
    bs_blk = first.shape[2]
    max_len = tables.shape[1] * bs_blk
    b, k = toks.shape
    x = tfm.embed_lookup(params["embed"], toks, compute_dtype)  # [B,k,D]
    positions = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    gate = active[:, None, None, None]

    def write_chunk(c, new):
        return _write_view(c, new, pos, gate)

    def write_scale_chunk(sc, new):
        return _write_view_scale(sc, new, pos, gate)

    mask = (
        jnp.arange(max_len)[None, None, :] <= positions[:, :, None]
    )  # [B, k, max_len]

    def body(carry, layer):
        x = carry
        if quantized:
            blk, ka, ksc, va, vsc = layer
        else:
            blk, ka, va = layer
        bsz = x.shape[0]
        q, kk, v = tfm.block_qkv(x, blk, n_heads, positions)
        if quantized:
            k8, ks = quantize_kv(kk)
            v8, vs = quantize_kv(v)
            fresh = (k8, ks, v8, vs)
            ck = dequantize_kv(
                write_chunk(_take_layer(ka, tables), k8),
                write_scale_chunk(_take_layer(ksc, tables), ks),
            )
            cv = dequantize_kv(
                write_chunk(_take_layer(va, tables), v8),
                write_scale_chunk(_take_layer(vsc, tables), vs),
            )
        else:
            fresh = (kk, v)
            ck = write_chunk(_take_layer(ka, tables), kk)
            cv = write_chunk(_take_layer(va, tables), v)
        o = tfm.cache_attention(q, ck, cv, mask)
        o = o.astype(x.dtype).reshape(bsz, k, -1)
        x = x + o @ tfm.wt(blk["wo"], x.dtype)
        x = tfm.block_ffn(x, blk)
        return x, fresh

    if quantized:
        (ka, ksc), (va, vsc) = arena
        xs = (params["blocks"], ka, ksc, va, vsc)
    else:
        xs = (params["blocks"],) + tuple(arena)
    x, fresh_layers = jax.lax.scan(body, x, xs)
    arena = write_fresh_window(
        arena, tables, fresh_layers, pos, k, active, quantized
    )
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    return logits, arena


def paged_attention_ref(q, ck, cv, tables, pos, fresh_kv,
                        k_scale=None, v_scale=None,
                        scale: Optional[float] = None):
    """jnp online-softmax reference of the Pallas block-table kernel.

    q [B,1,H,Dh]; ck/cv [N, bs, KV, Dh] arena leaves (int8 with
    ``k_scale``/``v_scale`` [N, bs, KV]); tables [B, nb]; pos [B] is
    the HISTORY length (positions 0..pos-1 live in blocks);
    ``fresh_kv = (fk, fv)`` [B,1,KV,Dh] is the pending token's K/V,
    folded LAST (it is position pos, the highest live column, so the
    per-block reduction order equals position order). One take per
    logical block, the flash recurrence across blocks; scratch-mapped
    and beyond-fill columns get softmax weight EXACTLY zero (and their
    V rows are zeroed before the weighted sum), so arbitrary scratch
    content can never leak into the output."""
    b, _, h, hd = q.shape
    n_kv = ck.shape[2]
    g = h // n_kv
    bs = ck.shape[1]
    nb = tables.shape[1]
    sc = scale if scale is not None else 1.0 / (hd ** 0.5)
    fk, fv = fresh_kv
    # GQA folding as in tfm.cache_attention: query heads group over the
    # compact KV heads, no repeat_kv expansion
    q5 = q.astype(jnp.float32)[:, 0].reshape(b, n_kv, g, hd)
    m = jnp.full((b, n_kv, g), NEG_INF, jnp.float32)
    l = jnp.zeros((b, n_kv, g), jnp.float32)
    acc = jnp.zeros((b, n_kv, g, hd), jnp.float32)
    hist = jnp.minimum(pos, nb * bs)
    for kb in range(nb):
        phys = tables[:, kb]
        kblk = jnp.take(ck, phys, axis=0).astype(jnp.float32)  # [B,bs,KV,hd]
        vblk = jnp.take(cv, phys, axis=0).astype(jnp.float32)
        if k_scale is not None:
            kblk = kblk * jnp.take(k_scale, phys, axis=0)[..., None]
            vblk = vblk * jnp.take(v_scale, phys, axis=0)[..., None]
        s = jnp.einsum("bkgd,bskd->bkgs", q5, kblk) * sc  # [B,KV,g,bs]
        cols = kb * bs + jnp.arange(bs, dtype=jnp.int32)
        live = cols[None, :] < hist[:, None]               # [B, bs]
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        vblk = jnp.where(live[:, :, None, None], vblk, 0.0)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_new))
        p = jnp.where(
            m_new[..., None] <= NEG_INF, 0.0, jnp.exp(s - m_new[..., None])
        )
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgs,bskd->bkgd", p, vblk)
        m = m_new
    fkf = fk.astype(jnp.float32)[:, 0]  # [B, KV, hd]
    fvf = fv.astype(jnp.float32)[:, 0]
    s1 = jnp.einsum("bkgd,bkd->bkg", q5, fkf) * sc
    m_new = jnp.maximum(m, s1)
    alpha = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_new))
    p1 = jnp.exp(s1 - m_new)  # the fresh column is always live
    l = l * alpha + p1
    acc = acc * alpha[..., None] + p1[..., None] * fvf[:, :, None, :]
    l2 = l[..., None]
    o = jnp.where(l2 > 0, acc / jnp.maximum(l2, 1e-30), 0.0)
    return o.reshape(b, 1, h, hd)


def block_attention(q, cache_k, cache_v, tables, pos, fresh_kv,
                    impl: str = "auto", interpret: Optional[bool] = None):
    """Block-table decode attention with PR-12-style impl dispatch:
    ``impl="auto"`` runs the Pallas kernel
    (ops/pallas/paged_attention.py) on a real TPU backend and
    :func:`paged_attention_ref` elsewhere; ``"pallas"`` forces the
    kernel (interpret-mode off-TPU), ``"jnp"`` forces the reference.
    ``cache_k``/``cache_v`` are arena layer leaves — ``[N, bs, KV, Dh]``
    float, or ``(int8 payload, [N, bs, KV] scales)`` tuples."""
    if impl not in ("auto", "jnp", "pallas"):
        raise ValueError(f"block_attention impl {impl!r} not auto/jnp/pallas")
    from nnstreamer_tpu.ops.dispatch import record as _record_dispatch
    from nnstreamer_tpu.ops.pallas._compat import pallas_ok

    use_pallas = impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu"
    )
    if use_pallas:
        # registry dtype gate: an unsupported arena dtype degrades to
        # the jnp reference with a logged reason
        payload = cache_k[0] if isinstance(cache_k, tuple) else cache_k
        use_pallas, _ = pallas_ok("paged_decode_attention", payload.dtype)
    _record_dispatch("block_attention", "pallas" if use_pallas else "jnp")
    if use_pallas:
        from nnstreamer_tpu.ops.pallas.paged_attention import (
            make_paged_attention,
        )

        return make_paged_attention(interpret=interpret)(
            q, cache_k, cache_v, tables, pos, fresh_kv
        )
    if isinstance(cache_k, tuple):
        (k8, ks), (v8, vs) = cache_k, cache_v
        return paged_attention_ref(
            q, k8, v8, tables, pos, fresh_kv, k_scale=ks, v_scale=vs
        )
    return paged_attention_ref(q, cache_k, cache_v, tables, pos, fresh_kv)
