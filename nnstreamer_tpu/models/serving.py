"""Continuous-batching LLM serving: slot-based KV-cache decode.

models/decode.py serves one request at a time; real serving multiplexes
many streams of different lengths onto one chip. The TPU-shaped answer is
slot-based continuous batching: a fixed [n_slots] batch of KV-cache slots,
one batched decode program stepping ALL active slots per token, and
requests joining/leaving between steps — shapes never change, so XLA
compiles a fixed handful of programs for the server's lifetime.

This is the genuinely-new analogue of the reference's one-server-many-
clients query path (tensor_query_serversrc client_id demultiplexing,
gst/nnstreamer/tensor_query/tensor_query_serversrc.c:379-427): there the
multiplexed unit is a frame, here it is a decode step.

Correctness invariant (tested): a request served in a busy batch yields
byte-identical greedy tokens to models/decode.generate() run alone —
per-slot positions, per-slot masks, and inactive-slot write gating make
slots fully isolated.

Design notes:
- per-slot RoPE positions (`pos` [B]) — rope() here takes per-batch
  positions, unlike the shared-position prefill path;
- cache writes go through a batched dynamic_update_slice (vmap over the
  slot axis) and are gated by `active`, so idle slots never mutate;
- prompts are right-padded to a fixed prompt bucket; causal masking makes
  the pad positions unreachable (they are never attended and the cache
  beyond the true length is rewritten before the mask can include it);
- ``cache_dtype="int8"`` stores the KV cache quantized (per-token-per-
  head scales, quantize_kv) — 4× less HBM than f32, i.e. 4× the live
  context per chip, dequantized on the attention read (blockwise in VMEM
  when the Pallas kernel runs, so HBM traffic stays at the int8 bytes);
- sampling (temperature / top-k / top-p) runs INSIDE the step program
  with per-slot parameters and per-slot fold_in(seed, position) keys —
  one int32 per slot crosses to host per step, never [B, V] logits;
- admission decouples from decode: submit() prefills outside the state
  lock and queues a pending insert that the next step() applies, so the
  compiled step runs with no lock held and admission never serializes
  behind an in-flight device step.
"""

from __future__ import annotations

import functools
import threading
import time as _time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from nnstreamer_tpu import trace as _trace
from nnstreamer_tpu.compile_cache import ensure_compile_cache
from nnstreamer_tpu.kv import block_attn as kvb
from nnstreamer_tpu.kv import gather as kvg
from nnstreamer_tpu.kv.blocks import BlockPool
from nnstreamer_tpu.kv.gather import dequantize_kv, quantize_kv
from nnstreamer_tpu.kv.sched import SLOLedger
from nnstreamer_tpu.models import decode as dec
from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.family import DenseFamily, refuse_unsupported
from nnstreamer_tpu.models.speculative import ngram_lookup
from nnstreamer_tpu.obs import metrics as _obs_metrics
from nnstreamer_tpu.ops.dispatch import record as _record_dispatch
from nnstreamer_tpu.parallel.mesh import batch_sharding


def batched_decode_step(
    params: Dict,
    tok,
    pos,
    active,
    cache: Tuple[jax.Array, jax.Array],
    n_heads: int,
    compute_dtype=jnp.float32,
    attn_fn=None,
    windowed: bool = False,
):
    """One decode step for a whole slot batch.

    tok [B] int32, pos [B] int32 (per-slot fill level), active [B] bool →
    (logits [B, V] f32, cache', pos'). Inactive slots: cache and pos are
    unchanged and their logits are garbage (callers must gate on
    ``active``). ``attn_fn(q, ck, cv, pos) -> [B,1,H,Dh]`` overrides the
    inline masked attention (the Pallas single-pass kernel,
    ops/pallas/decode_attention.py); with an int8 cache the attn_fn
    receives the quantized entries ``(ck8, kscale)`` / ``(cv8, vscale)``
    directly — the kernel dequantizes blockwise in VMEM, which is the
    whole point of quantizing (HBM traffic stays at int8 bytes).

    ``cache`` is either ``(ck, cv)`` (float) or
    ``((ck8, kscale), (cv8, vscale))`` (int8, see quantize_kv).

    ``windowed=True`` treats the cache's length dim as a RING over the
    last max_len tokens (sliding-window attention): writes land at
    ``pos % max_len``, and that is the ONLY change — the ≤pos liveness
    mask saturates to all-live once pos ≥ max_len, which is exactly the
    ring's semantics (every entry then holds one of the last max_len
    tokens). K rows are stored already RoPE-rotated at their absolute
    position, so the softmax needs only the *set* of the last-W keys,
    never their ring order; ``pos`` keeps counting absolute tokens,
    which keeps RoPE exact for as long as f32 can hold the position
    (~16.7M tokens — rope() computes angles in float32).
    The same saturation argument makes windowed compose with attn_fn
    (the Pallas kernel's ``cols ≤ pos`` mask degenerates identically)."""
    quantized = isinstance(cache[0], tuple)
    max_len = (cache[0][0] if quantized else cache[0]).shape[2]
    b = tok.shape[0]
    x = tfm.embed_lookup(params["embed"], tok, compute_dtype)[:, None, :]
    gate = active[:, None, None, None]
    wpos = pos % max_len if windowed else pos

    def write(c, new):
        """c [B,max_len,H,Dh] ← new [B,1,H,Dh] at per-slot pos, if active."""
        written = jax.vmap(
            lambda cb, nb, p: jax.lax.dynamic_update_slice(cb, nb, (p, 0, 0))
        )(c, new.astype(c.dtype), wpos)
        return jnp.where(gate, written, c)

    def write_scale(sc, new):
        """sc [B,max_len,H] ← new [B,1,H] at per-slot pos, if active."""
        written = jax.vmap(
            lambda sb, nb, p: jax.lax.dynamic_update_slice(sb, nb, (p, 0))
        )(sc, new, wpos)
        return jnp.where(gate[..., 0], written, sc)

    def body(carry, layer):
        x = carry
        if quantized:
            blk, ck8, ksc, cv8, vsc = layer
        else:
            blk, ck, cv = layer
        bsz, _, d = x.shape
        with jax.named_scope("nns.attn"):
            # per-slot positions: block_qkv → rope() take [B,T] (here T=1);
            # k/v come back with KV ≤ H heads (GQA) matching the cache
            q, k, v = tfm.block_qkv(x, blk, n_heads, pos[:, None])
            if quantized:
                k8, ks = quantize_kv(k)
                v8, vs = quantize_kv(v)
                ck8 = write(ck8, k8)
                ksc = write_scale(ksc, ks)
                cv8 = write(cv8, v8)
                vsc = write_scale(vsc, vs)
                out_layer = (ck8, ksc, cv8, vsc)
                if attn_fn is None:
                    ck = dequantize_kv(ck8, ksc)
                    cv = dequantize_kv(cv8, vsc)
            else:
                ck = write(ck, k)
                cv = write(cv, v)
                out_layer = (ck, cv)
            if attn_fn is not None:
                if quantized:
                    o = attn_fn(q, (ck8, ksc), (cv8, vsc), pos)
                else:
                    o = attn_fn(q, ck, cv, pos)  # [B,1,H,Dh] f32
            else:
                # liveness mask [B, max_len]: the ≤pos prefix — which
                # saturates to all-live past a ring wrap (windowed), exactly
                # the last-W-tokens semantics
                mask = jnp.arange(max_len)[None, :] <= pos[:, None]
                o = tfm.cache_attention(q, ck, cv, mask[:, None, :])
            o = o.astype(x.dtype).reshape(bsz, 1, -1)
            x = x + o @ tfm.wt(blk["wo"], x.dtype)
        with jax.named_scope("nns.ffn"):
            x = tfm.block_ffn(x, blk)
        return x, out_layer

    if quantized:
        (ck8, ksc), (cv8, vsc) = cache
        xs = (params["blocks"], ck8, ksc, cv8, vsc)
    else:
        xs = (params["blocks"],) + tuple(cache)
    x, out_layers = jax.lax.scan(body, x, xs)
    if quantized:
        ck8, ksc, cv8, vsc = out_layers
        cache_out = ((ck8, ksc), (cv8, vsc))
    else:
        cache_out = out_layers
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)[:, 0]
    return logits, cache_out, pos + active.astype(jnp.int32)


def batched_verify_step(
    params: Dict,
    toks,
    pos,
    active,
    cache: Tuple[jax.Array, jax.Array],
    n_heads: int,
    compute_dtype=jnp.float32,
):
    """Score per-slot k-token candidate chunks in ONE forward — the
    continuous-batching speculation verify (models/speculative.py's
    _verify generalized to per-slot positions, the same way
    batched_decode_step generalizes decode_step).

    toks [B, k] int32 (row 0 = the slot's pending token, rows 1..k-1 =
    proposals), pos [B] (per-slot fill), active [B] →
    (logits [B, k, V] f32, cache'). Chunk K/V land at per-slot positions
    pos..pos+k-1, gated on ``active``; the caller advances each slot's
    pos by its accepted count — rejected positions are overwritten
    before any mask can reach them (verify_chunk's invariant, held
    per slot). Caller must guarantee pos + k ≤ max_len for every active
    slot (dynamic_update_slice would clamp and corrupt otherwise)."""
    quantized = isinstance(cache[0], tuple)
    max_len = (cache[0][0] if quantized else cache[0]).shape[2]
    b, k = toks.shape
    x = tfm.embed_lookup(params["embed"], toks, compute_dtype)  # [B,k,D]
    positions = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    gate = active[:, None, None, None]

    def write_chunk(c, new):
        """c [B,max_len,H,Dh] ← new [B,k,H,Dh] at per-slot pos."""
        written = jax.vmap(
            lambda cb, nb, p: jax.lax.dynamic_update_slice(cb, nb, (p, 0, 0))
        )(c, new.astype(c.dtype), pos)
        return jnp.where(gate, written, c)

    def write_scale_chunk(sc, new):
        written = jax.vmap(
            lambda sb, nb, p: jax.lax.dynamic_update_slice(sb, nb, (p, 0))
        )(sc, new, pos)
        return jnp.where(gate[..., 0], written, sc)

    # per-slot causal mask over the cache: query i attends ≤ pos_b + i
    mask = (
        jnp.arange(max_len)[None, None, :] <= positions[:, :, None]
    )  # [B, k, max_len]

    def body(carry, layer):
        x = carry
        if quantized:
            blk, ck8, ksc, cv8, vsc = layer
        else:
            blk, ck, cv = layer
        bsz = x.shape[0]
        q, kk, v = tfm.block_qkv(x, blk, n_heads, positions)
        if quantized:
            k8, ks = quantize_kv(kk)
            v8, vs = quantize_kv(v)
            ck8 = write_chunk(ck8, k8)
            ksc = write_scale_chunk(ksc, ks)
            cv8 = write_chunk(cv8, v8)
            vsc = write_scale_chunk(vsc, vs)
            ck = dequantize_kv(ck8, ksc)
            cv = dequantize_kv(cv8, vsc)
            out_layer = (ck8, ksc, cv8, vsc)
        else:
            ck = write_chunk(ck, kk)
            cv = write_chunk(cv, v)
            out_layer = (ck, cv)
        o = tfm.cache_attention(q, ck, cv, mask)
        o = o.astype(x.dtype).reshape(bsz, k, -1)
        x = x + o @ tfm.wt(blk["wo"], x.dtype)
        x = tfm.block_ffn(x, blk)
        return x, out_layer

    if quantized:
        (ck8, ksc), (cv8, vsc) = cache
        xs = (params["blocks"], ck8, ksc, cv8, vsc)
    else:
        xs = (params["blocks"],) + tuple(cache)
    x, out_layers = jax.lax.scan(body, x, xs)
    if quantized:
        ck8, ksc, cv8, vsc = out_layers
        cache_out = ((ck8, ksc), (cv8, vsc))
    else:
        cache_out = out_layers
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    return logits, cache_out


def _ring_live_mask(pos, W: int, row):
    """Ring-row liveness for chunk queries on a pre-write W-ring.

    pos [B] absolute fill, row [R] chunk-column indices → [B, R, W]
    bool: ring slot s last held absolute position pos-1-d where
    d = (wp-1-s) mod W (wp = pos % W); it is attendable by the query in
    chunk column r (absolute position pos+r) iff written (d ≤ pos-1)
    and inside the window (d ≤ W-2-r). ONE definition shared by the
    target's verify and the draft's propose — the two masks must never
    drift apart (a divergence only degrades acceptance, silently)."""
    wp = pos % W
    d = (wp[:, None] - 1 - jnp.arange(W, dtype=jnp.int32)[None, :]) % W
    return (
        d[:, None, :]
        <= jnp.minimum(pos[:, None] - 1, W - 2 - row[None, :])[:, :, None]
    )


def batched_windowed_verify(
    params: Dict,
    toks,
    pos,
    active,
    cache,
    n_heads: int,
    compute_dtype=jnp.float32,
):
    """Per-slot k-chunk scoring against a RING cache WITHOUT writing it.

    The windowed sibling of batched_verify_step. In-place chunk writes
    on a ring would clobber live history: column j's row (pos+j) % W
    still holds absolute position pos+j-W, which stays inside the
    attention window of every query before pos+j — so the forward runs
    against the PRE-write ring concatenated with the chunk's own fresh
    K/V (decode.windowed_chunk's formulation, generalized to per-slot
    positions), and returns the chunk K/V for commit_ring_chunk to
    write AFTER acceptance is known (only accepted columns land, so
    rejected proposals never destroy window content).

    toks [B, k], pos [B] (absolute fill), ring cache [L, B, W, KV, Dh]
    (float, or the int8 ((ck8, ksc), (cv8, vsc)) layout) →
    (logits [B, k, V] f32, chunk_ks [L, B, k, KV, Dh],
    chunk_vs [L, B, k, KV, Dh]) — chunk K/V in compute dtype.

    Masking (per slot b, query row i at absolute p = pos_b + i):
    ring row s last held absolute position pos_b - 1 - d where
    d = (wp_b - 1 - s) mod W (wp_b = pos_b % W); it is attendable iff
    written (d ≤ pos_b - 1) and inside the window (d ≤ W - 2 - i).
    Chunk rows are causal (j ≤ i; k ≤ W keeps them all in-window)."""
    quantized = isinstance(cache[0], tuple)
    ring_k = cache[0][0] if quantized else cache[0]
    W = ring_k.shape[2]
    b, k = toks.shape
    x = tfm.embed_lookup(params["embed"], toks, compute_dtype)  # [B,k,D]
    positions = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    row = jnp.arange(k, dtype=jnp.int32)
    ring_mask = _ring_live_mask(pos, W, row)  # [B, k, W]
    chunk_mask = jnp.broadcast_to(
        row[None, None, :] <= row[None, :, None], (b, k, k)
    )
    mask = jnp.concatenate([ring_mask, chunk_mask], axis=2)  # [B, k, W+k]

    def body(carry, layer):
        x = carry
        if quantized:
            blk, ck8, ksc, cv8, vsc = layer
            ck = dequantize_kv(ck8, ksc)
            cv = dequantize_kv(cv8, vsc)
        else:
            blk, ck, cv = layer
        q, kk, v = tfm.block_qkv(x, blk, n_heads, positions)
        if quantized:
            # attend the quantize→dequantize roundtrip of the fresh
            # chunk K/V — exactly what a plain int8 step attends after
            # its pre-attention cache write, so greedy spec rounds stay
            # byte-identical to plain int8 stepping (commit re-quantizes
            # the raw K/V, which lands the same int8 payload)
            ka = dequantize_kv(*quantize_kv(kk)).astype(kk.dtype)
            va = dequantize_kv(*quantize_kv(v)).astype(v.dtype)
        else:
            ka, va = kk, v
        o = tfm.cache_attention(
            q,
            jnp.concatenate([ck.astype(kk.dtype), ka], axis=1),
            jnp.concatenate([cv.astype(v.dtype), va], axis=1),
            mask,
        )
        o = o.astype(x.dtype).reshape(b, k, -1)
        x = x + o @ tfm.wt(blk["wo"], x.dtype)
        x = tfm.block_ffn(x, blk)
        return x, (kk, v)

    if quantized:
        (ck8, ksc), (cv8, vsc) = cache
        xs = (params["blocks"], ck8, ksc, cv8, vsc)
    else:
        xs = (params["blocks"],) + tuple(cache)
    x, (chunk_ks, chunk_vs) = jax.lax.scan(body, x, xs)
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    return logits, chunk_ks, chunk_vs


def commit_ring_chunk(cache, chunk_ks, chunk_vs, pos, n_commit, active):
    """Write the first ``n_commit[b]`` chunk columns into the ring at
    rows (pos_b + j) % W, gated on ``active`` — the post-acceptance
    commit paired with batched_windowed_verify (only certified columns
    may overwrite window history). Handles the per-column ring wrap
    (unlike the contiguous prefill write, a decode-time chunk may start
    anywhere in the ring). Quantizes when the cache is int8."""
    quantized = isinstance(cache[0], tuple)
    ring_k = cache[0][0] if quantized else cache[0]
    W = ring_k.shape[2]
    k = chunk_ks.shape[2]

    def write_col(c, col, rows, keep):
        """c [L,B,W,...] ← col [L,B,...] at per-slot ring row, gated."""
        cb = jnp.moveaxis(c, 1, 0)  # [B, L, W, ...]
        nb = jnp.moveaxis(col[:, :, None], 1, 0)  # [B, L, 1, ...]
        start = (0,) * (cb.ndim - 2)
        written = jax.vmap(
            lambda cs, ns, r: jax.lax.dynamic_update_slice(
                cs, ns.astype(cs.dtype), (0, r) + start[1:]
            )
        )(cb, nb, rows)
        gate = keep.reshape((-1,) + (1,) * (cb.ndim - 1))
        return jnp.moveaxis(jnp.where(gate, written, cb), 0, 1)

    for j in range(k):
        rows = (pos + j) % W
        keep = active & (j < n_commit)
        kj = chunk_ks[:, :, j]  # [L, B, KV, Dh]
        vj = chunk_vs[:, :, j]
        if quantized:
            (ck8, ksc), (cv8, vsc) = cache
            k8, ks = quantize_kv(kj)
            v8, vs = quantize_kv(vj)
            cache = (
                (write_col(ck8, k8, rows, keep),
                 write_col(ksc, ks, rows, keep)),
                (write_col(cv8, v8, rows, keep),
                 write_col(vsc, vs, rows, keep)),
            )
        else:
            ck, cv = cache
            cache = (
                write_col(ck, kj, rows, keep),
                write_col(cv, vj, rows, keep),
            )
    return cache


def draft_windowed_propose(
    params: Dict,
    tok,
    pos,
    cache,
    n_heads: int,
    k: int,
    compute_dtype=jnp.float32,
):
    """k-1 greedy draft proposals per slot against a RING cache WITHOUT
    writing it — the draft-side sibling of batched_windowed_verify.

    A draft stepping a ring in place would clobber window history with
    K/V of proposals the target then rejects (the same hazard the
    target's verify avoids). So the whole k-step chain runs in one
    program against the PRE-write ring plus the chain's own fresh chunk
    K/V (column j attends ring rows inside position pos+j's window and
    chunk columns ≤ j), accumulating the chunk in a fixed [L, B, k]
    buffer; commit_ring_chunk later lands only the accepted columns.

    tok [B] (pending tokens, chunk column 0), pos [B] absolute fill →
    (props [B, k-1] int32, chunk_ks, chunk_vs [L, B, k, KV, Dh]).
    Inactive slots are NOT gated here — their proposals are garbage the
    caller ignores, and commit_ring_chunk's ``active`` gate keeps their
    writes out of the ring (the draft ring is always float; a quantized
    target cache never makes the draft's quantized)."""
    ring_k = cache[0]
    L = ring_k.shape[0]
    W = ring_k.shape[2]
    b = tok.shape[0]
    kv = ring_k.shape[3]
    hd = ring_k.shape[4]
    chunk_ks = jnp.zeros((L, b, k, kv, hd), compute_dtype)
    chunk_vs = jnp.zeros((L, b, k, kv, hd), compute_dtype)
    toks0 = jnp.zeros((b, k), jnp.int32).at[:, 0].set(tok)

    def step(carry, j):
        cur, cks, cvs, toks = carry
        x = tfm.embed_lookup(params["embed"], cur, compute_dtype)[:, None, :]
        positions = (pos + j)[:, None]
        ring_mask = _ring_live_mask(pos, W, j[None])  # [B, 1, W]
        chunk_mask = (
            jnp.arange(k, dtype=jnp.int32)[None, None, :] <= j
        )  # [1, 1, k] — columns ≤ j (col j written below before attend)
        mask = jnp.concatenate(
            [ring_mask, jnp.broadcast_to(chunk_mask, (b, 1, k))], axis=2
        )

        def body(xc, layer):
            x = xc
            blk, ck, cv, cks_l, cvs_l = layer
            q, kk, v = tfm.block_qkv(x, blk, n_heads, positions)
            cks_l = jax.lax.dynamic_update_slice(
                cks_l, kk.astype(cks_l.dtype), (0, j, 0, 0)
            )
            cvs_l = jax.lax.dynamic_update_slice(
                cvs_l, v.astype(cvs_l.dtype), (0, j, 0, 0)
            )
            o = tfm.cache_attention(
                q,
                jnp.concatenate([ck.astype(cks_l.dtype), cks_l], axis=1),
                jnp.concatenate([cv.astype(cvs_l.dtype), cvs_l], axis=1),
                mask,
            )
            o = o.astype(x.dtype).reshape(b, 1, -1)
            x = x + o @ tfm.wt(blk["wo"], x.dtype)
            x = tfm.block_ffn(x, blk)
            return x, (cks_l, cvs_l)

        xs = (params["blocks"],) + tuple(cache) + (cks, cvs)
        x, (cks, cvs) = jax.lax.scan(body, x, xs)
        x = tfm.rmsnorm(x, params["ln_f"])
        logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        toks = jnp.where(
            (j + 1 < k), toks.at[:, jnp.minimum(j + 1, k - 1)].set(nxt), toks
        )
        return (nxt, cks, cvs, toks), None

    (_, chunk_ks, chunk_vs, toks), _ = jax.lax.scan(
        step, (tok, chunk_ks, chunk_vs, toks0),
        jnp.arange(k, dtype=jnp.int32),
    )
    return toks[:, 1:], chunk_ks, chunk_vs


def spec_accept(logits, toks, temp, topk, topp, keys, pos, sampling: bool):
    """Device-side acceptance for one speculative round.

    logits [B, k, V] (column j conditioned on toks[:, :j+1]), toks
    [B, k] (column 0 = the pending token, columns 1.. = proposals; -1
    marks a no-proposal column), per-slot sampling params, base keys
    [B, 2], pos [B] → (m [B] int32, final [B] int32). ``m`` is the
    count of committed chunk columns (1 + accepted proposals); the
    round emits toks[:, 1:m] then ``final``.

    Greedy slots (temp ≤ 0) accept while the previous column's argmax
    equals the proposal — byte-identical to plain step()s by
    construction. Sampling slots use point-mass rejection sampling
    (Leviathan et al. with a deterministic draft): accept proposal x
    with probability p̃(x) under the SAME filtered distribution
    sample_tokens draws from, else resample from the renormalized
    remainder (p̃ with x removed) — every emitted token is distributed
    exactly as a plain sampling step's, though the stream is keyed
    per (seed, fill, draw) rather than (seed, fill), so it is
    distribution-exact, not byte-identical, to step() output.
    ``sampling`` is a static flag: the greedy-only program compiles
    without the filtering/PRNG work."""
    b, k, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k]
    if not sampling:
        props = toks[:, 1:]  # [B, k-1]
        match = props == greedy[:, :-1]
        # m-1 = length of the accepted prefix of proposals
        acc_len = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        m = 1 + acc_len.astype(jnp.int32)
        final = jnp.take_along_axis(greedy, (m - 1)[:, None], axis=1)[:, 0]
        return m, final

    is_sampling = temp > 0  # [B] — mixed batches certify per slot
    logits_t = jnp.moveaxis(logits, 1, 0)  # [k, B, V]
    toks_t = toks.T  # [k, B]

    def col(carry, xs):
        m, done, final = carry
        j, lg, prop = xs  # column j ∈ 1..k-1; lg = logits[:, j-1]
        greedy_col = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        valid = prop >= 0
        kj = jax.vmap(jax.random.fold_in)(keys, pos + j)
        k_acc = jax.vmap(jax.random.fold_in)(kj, jnp.ones((b,), jnp.int32))
        k_res = jax.vmap(jax.random.fold_in)(
            kj, jnp.full((b,), 2, jnp.int32)
        )
        filt = _filtered_logits(lg, temp, topk, topp)
        probs = jax.nn.softmax(filt, axis=-1)
        p_prop = jnp.take_along_axis(
            probs, jnp.clip(prop, 0, v - 1)[:, None], axis=-1
        )[:, 0]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(k_acc)
        acc = jnp.where(is_sampling, u < p_prop, greedy_col == prop) & valid
        # rejection final: residual distribution (p̃ minus the point
        # mass) for a real proposal; a plain p̃ sample for a
        # no-proposal column (that column IS a plain step)
        residual = jnp.where(
            jax.nn.one_hot(jnp.clip(prop, 0, v - 1), v, dtype=bool)
            & valid[:, None],
            -jnp.inf,
            filt,
        )
        resampled = jax.vmap(jax.random.categorical)(
            k_res, residual
        ).astype(jnp.int32)
        final_rej = jnp.where(is_sampling, resampled, greedy_col)
        rejecting = (~done) & (~acc)
        final = jnp.where(rejecting, final_rej, final)
        m = m + ((~done) & acc).astype(jnp.int32)
        done = done | rejecting
        return (m, done, final), None

    init = (
        jnp.ones((b,), jnp.int32),
        jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32),
    )
    (m, done, final), _ = jax.lax.scan(
        col,
        init,
        (jnp.arange(1, k, dtype=jnp.int32), logits_t[:-1], toks_t[1:]),
    )
    # full acceptance: bonus token from the last column at fill pos+k
    kb = jax.vmap(jax.random.fold_in)(keys, pos + k)
    k_bonus = jax.vmap(jax.random.fold_in)(kb, jnp.ones((b,), jnp.int32))
    bonus = sample_tokens(logits[:, k - 1], temp, topk, topp, k_bonus)
    return m, jnp.where(done, final, bonus)


def _filtered_logits(logits, temp, top_k, top_p):
    """Temperature-scaled, top-k/top-p-filtered logits [B, V] — the
    distribution every sampling decision (plain step, speculative
    acceptance, rejection resample) draws from, factored out so the
    speculative path certifies against EXACTLY what sample_tokens would
    have sampled."""
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    # top-k: threshold at the k-th largest value per row where enabled
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=-1
    )
    scaled = jnp.where((top_k > 0)[:, None] & (scaled < kth), -jnp.inf, scaled)
    # top-p over the (possibly top-k-truncated) distribution
    probs = jax.nn.softmax(scaled, axis=-1)
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    csum = jnp.cumsum(sp, axis=-1)
    n_keep = jnp.sum(csum < top_p[:, None], axis=-1) + 1
    cutoff = jnp.take_along_axis(
        sp, jnp.clip(n_keep - 1, 0, v - 1)[:, None], axis=-1
    )
    return jnp.where(
        (top_p < 1.0)[:, None] & (probs < cutoff), -jnp.inf, scaled
    )


def sample_tokens(logits, temp, top_k, top_p, keys):
    """Per-slot token selection INSIDE the step program.

    logits [B, V] f32; temp [B] f32 (≤ 0 → greedy); top_k [B] int32
    (0 → disabled); top_p [B] f32 (1.0 → disabled; the nucleus keeps the
    smallest most-probable set with mass ≥ top_p, boundary token
    included); keys [B, 2] uint32 per-slot PRNG keys → tok [B] int32.
    Everything is branch-free so one compiled program serves any mix of
    greedy and sampling slots — and only [B] token ids ever cross to the
    host, never the [B, V] logits (at a 32k–128k vocab that transfer is
    megabytes per step)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _filtered_logits(logits, temp, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


def insert_slot(cache, ks, vs, slot):
    """Write one prefilled request's K/V [L,1,P,H,Dh] into cache slot
    ``slot`` (quantizing when the cache is int8). Stale positions beyond
    P from a previous occupant are harmless: the decode mask only ever
    covers positions the new occupant has itself written (each step
    writes position ``pos`` before the mask grows to include it)."""

    def put(c, new):
        # [L, B, max_len, H, Dh]; write [L, 1, P, H, Dh] at (0, slot, 0)
        return jax.lax.dynamic_update_slice(
            c, new.astype(c.dtype), (0, slot, 0, 0, 0)
        )

    def put_scale(sc, new):
        # [L, B, max_len, H] ← [L, 1, P, H]
        return jax.lax.dynamic_update_slice(sc, new, (0, slot, 0, 0))

    if isinstance(cache[0], tuple):
        (ck8, ksc), (cv8, vsc) = cache
        k8, kscale = quantize_kv(ks)
        v8, vscale = quantize_kv(vs)
        return (
            (put(ck8, k8), put_scale(ksc, kscale)),
            (put(cv8, v8), put_scale(vsc, vscale)),
        )
    cache_k, cache_v = cache
    return put(cache_k, ks), put(cache_v, vs)


def hist_write_row(hist, row, start, count, wrap: bool = False):
    """Scatter ``row`` [B, K] into the device token history ``hist``
    [B, H] at per-slot ``start`` [B], keeping only the first ``count``
    [B] columns per slot. ``wrap=True`` treats hist as a RING over the
    last H stream positions (token at absolute position a lives at
    a % H) — the windowed batcher's layout, mirroring its KV ring;
    without it, writes past H-1 clamp onto the last cell (unreachable
    on linear batchers, whose submit validates fill+budget ≤ H)."""
    _, H = hist.shape
    K = row.shape[1]
    raw = start[:, None] + jnp.arange(K)[None, :]
    idx = raw % H if wrap else jnp.clip(raw, 0, H - 1)
    keep = jnp.arange(K)[None, :] < count[:, None]

    def one(h, r, ix, kp):
        return h.at[ix].set(jnp.where(kp, r, h[ix]))

    return jax.vmap(one)(hist, row, idx, keep)


def device_ngram_propose(hist, pos, k: int, g: int, wrap: bool = False):
    """Prompt-lookup proposals ON DEVICE — no host round trip.

    The host n-gram path (ngram_lookup over req.tokens) costs two
    device→host reads per round (pos, tok) plus Python mining; each
    read is a full host↔device sync, so mining must happen where the
    tokens already are. ``hist`` [B, H] int32 is the
    per-slot token history (-1 padded), ``pos`` [B] the pending token's
    index (invariant: hist[pos] == pending token). Finds the most
    recent earlier occurrence of the suffix g-gram ending at ``pos``
    and proposes the k-1 tokens that followed it; -1 sentinels where
    the lookup finds nothing (sentinels can never be accepted —
    spec_accept's found-nothing discipline, serving.py spec_step).
    Role-match: the device form of the prompt-lookup proposer
    (models/speculative.ngram_lookup, vLLM-style self-drafting)."""
    _, H = hist.shape
    idx = jnp.arange(H)

    def one(h, p):
        if wrap:
            # unroll the ring into stream order: after a wrap the last
            # H tokens live at (p-H+1..p) % H; ordering them makes the
            # pending token the last element, so the same linear
            # matcher applies (before a wrap the ring IS linear)
            start = jnp.where(p >= H, (p + 1) % H, 0)
            h = h[(idx + start) % H]
            p = jnp.minimum(p, H - 1)
        ok = jnp.ones((H,), bool)
        for i in range(g):
            shifted = h[jnp.maximum(idx - i, 0)]
            tgt = h[jnp.maximum(p - i, 0)]
            ok &= (shifted == tgt) & (idx - i >= 0) & (p - i >= 0)
            ok &= shifted >= 0  # pad cells never participate
        ok &= idx < p  # the suffix itself is not a match
        j = jnp.max(jnp.where(ok, idx, -1))
        cols = j + 1 + jnp.arange(k - 1)
        valid = (j >= 0) & (cols <= p)  # only mined, known context
        return jnp.where(valid, h[jnp.clip(cols, 0, H - 1)], -1)

    return jax.vmap(one)(hist, pos)


def spec_emit_hist(toks, m, final, active, hist, pos_, windowed: bool):
    """Emitted row [B, k] for one speculative round — the m-1 accepted
    proposals then the correction/bonus token, -1 beyond — recorded into
    the device history so later rounds mine a complete context. ONE
    implementation shared by the slot and paged spec programs (the
    device-side form of spec_step's host commit loop)."""
    kk = toks.shape[1]
    j = jnp.arange(kk)[None, :]
    prop_part = jnp.concatenate(
        [toks[:, 1:], jnp.full((toks.shape[0], 1), -1, jnp.int32)],
        axis=1,
    )
    emit = jnp.where(
        j < (m - 1)[:, None], prop_part,
        jnp.where(j == (m - 1)[:, None], final[:, None], -1),
    )
    emit = jnp.where(active[:, None], emit, -1)
    hist = hist_write_row(hist, emit, pos_ + 1, m, wrap=windowed)
    return emit, hist


def request_key(seed) -> np.ndarray:
    """A request's base PRNG key, made on the host: bit for bit the two
    ``uint32`` words ``np.asarray(jax.random.PRNGKey(seed))`` gives under
    the default (threefry) key implementation — the seed's high and low
    32 bits, the high word 0 where jax would hold the seed in 32 bits (a
    Python int is an int64 first, as in jax; ``jax_enable_x64`` off
    narrows every seed). ``submit`` holds the state lock when it keys a
    request, and a device program there would queue behind the decode
    launch in flight: this touches no device."""
    s = np.int64(seed) if isinstance(seed, int) else np.asarray(seed)
    if s.shape or not np.issubdtype(s.dtype, np.integer):
        raise TypeError(f"a request seed is one integer, got {seed!r}")
    bits = int(s) & 0xFFFFFFFFFFFFFFFF
    wide = s.dtype.itemsize == 8 and jax.config.jax_enable_x64
    return np.array(
        [bits >> 32 if wide else 0, bits & 0xFFFFFFFF], np.uint32
    )


@dataclass
class _Request:
    rid: int
    budget: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_token: Optional[int] = None
    key: Optional[np.ndarray] = None  # base PRNG key [2] uint32
    prompt: Optional[np.ndarray] = None  # spec_step's proposal context
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    fill0: int = 0  # cache fill at admission; pos = fill0+len(tokens)-1

    def finished(self) -> bool:
        """Budget exhausted, or the stop token was emitted (which stays
        in the output, like an EOS id in any serving API)."""
        if len(self.tokens) >= self.budget:
            return True
        return bool(self.tokens) and self.tokens[-1] == self.stop_token


@dataclass
class _PendingInsert:
    """A prefilled request waiting for the next step() to splice its K/V
    into the batch cache (submit never touches device state directly, so
    the compiled step runs lock-free)."""

    slot: int
    ks: Optional[jax.Array]
    vs: Optional[jax.Array]
    first_tok: Any  # device int32 scalar (fetched at apply) or int
    fill: int  # cache fill level (= absolute position count)
    req: _Request
    draft_kv: Optional[Tuple[jax.Array, jax.Array]] = None
    hist_row: np.ndarray = None  # [max_len] device n-gram context seed
    blocks: Optional[List[int]] = None  # paged: the slot's block table
    resumed: bool = False  # paged: re-admission after preemption


@dataclass
class _PrefillBin:
    """Bucket-sized prompts that share one packed prefill program: the jobs
    in queue order and the rows they take (a prompt starts on a block
    boundary, so its last block's unused rows count)."""

    jobs: List[Any] = field(default_factory=list)
    rows: int = 0


def _named(fn, name: str):
    """``fn`` under a name of its own: a jitted lambda is ``jit__lambda``
    in a device trace, indistinguishable from every other one. The name
    becomes the XLA module's (``jit_<name>``), which is what a reader of
    the trace selects programs by."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _weights_jit(fn, weights, donate_argnums=(), name=None, **kw):
    """``jax.jit`` for a program that runs a model: ``fn(weights,
    *args)`` with ``weights`` bound as its first ARGUMENT (the returned
    callable takes ``*args`` only; ``donate_argnums`` counts them).
    ``name`` names the program in the device trace (:func:`_named`);
    the decode programs keep the ``impl`` they are known by there.

    A weight pytree that a jitted function merely closes over is
    lowered as CONSTANTS: serialized into every program's HLO, compiled
    with it, and held in HBM once per program. Harmless at the zoo's
    256-wide default; at d_model 2048 it is 1.6 GB per program — the
    first chip run died at 40 GiB of host memory compiling the batcher's
    programs (PERF.md, PR 21). As arguments, every program shares the
    one resident copy."""
    if isinstance(donate_argnums, int):
        donate_argnums = (donate_argnums,)
    if name is not None:
        fn = _named(fn, name)
    jitted = jax.jit(
        fn, donate_argnums=tuple(i + 1 for i in donate_argnums), **kw
    )
    return functools.partial(jitted, weights)


# columns of an admission row after the history's ``max_len``: the packed
# int32 buffer _apply_batch_locked ships (float32 and uint32 words ride as
# their bits)
_ADMIT_COLS = 8
(_ADM_LIVE, _ADM_TOK, _ADM_POS, _ADM_TOPK, _ADM_TEMP, _ADM_TOPP,
 _ADM_KEY) = range(7)  # _ADM_KEY holds two words


def _make_admit(max_len: int, vec_sh=None):
    """The admission program: every queued request's writes to the seven
    per-slot arrays (donated) in ONE launch, from ONE packed transfer.

    ``rows`` is int32 ``[n_slots, max_len + _ADMIT_COLS]``: row ``s`` holds
    what slot ``s`` takes (history row, then the ``_ADM_*`` columns) and
    counts only where its ``_ADM_LIVE`` column is set, so the shape is the
    batcher's own whatever the number admitted and nothing compiles after
    the first admission. ``jit_nns_admit`` on a device trace. On a mesh
    the arrays come back on ``vec_sh`` (what ``_pin`` does for eager
    updates)."""
    H = max_len

    def admit(tok, pos, temp, topk, topp, keys, hist, rows):
        live = rows[:, H + _ADM_LIVE] != 0

        def col(c, dtype=jnp.int32, n=1):
            v = rows[:, H + c: H + c + n]
            if dtype != jnp.int32:
                v = jax.lax.bitcast_convert_type(v, dtype)
            return v if n > 1 else v[:, 0]

        def put(old, new):
            m = live if old.ndim == 1 else live[:, None]
            return jnp.where(m, new, old)

        return (
            put(tok, col(_ADM_TOK)), put(pos, col(_ADM_POS)),
            put(temp, col(_ADM_TEMP, jnp.float32)),
            put(topk, col(_ADM_TOPK)),
            put(topp, col(_ADM_TOPP, jnp.float32)),
            put(keys, col(_ADM_KEY, jnp.uint32, 2)),
            put(hist, rows[:, :H]),
        )

    kw = {}
    if vec_sh is not None:
        kw = dict(in_shardings=(vec_sh,) * 8, out_shardings=(vec_sh,) * 7)
    return jax.jit(
        _named(admit, "nns_admit"), donate_argnums=tuple(range(7)), **kw
    )


def nns_sample_first(logits, temp, topk, topp, key, fill):
    """First-token pick: the step's device sampler over the prefill logits.
    Its arguments are host (numpy) values shipped by the call, the request
    key folded with the position inside the program: no eager launch
    builds them."""
    return sample_tokens(
        logits[None, :], temp, topk, topp,
        jax.random.fold_in(key, fill)[None],
    )[0]


# first tokens one launch of nns_sample_first_rows picks: the sampler's two
# sorts over the vocabulary cost by the row (2.0 ms for 32 rows of 32,000,
# 0.5 ms for one; PERF.md, PR 35) and a bin holds 1.3-1.6 prompts on average
_FIRST_ROWS = 8


def nns_sample_first_rows(logits, rows, temp, topk, topp, keys, fill):
    """``nns_sample_first`` for ``rows`` of a packed bucket's logits in one
    launch: the request of ``rows[r]`` gets the token ``nns_sample_first``
    would give it (the same ``fold_in(key, fill)``). A tuple of device
    scalars, so that each pending insert holds its own and no eager slice
    is launched for it."""
    toks = sample_tokens(
        logits[rows], temp, topk, topp,
        jax.vmap(jax.random.fold_in)(keys, fill),
    )
    return tuple(toks[r] for r in range(rows.shape[0]))


def nns_load_prefix(stage, ks, vs):
    return (
        jax.lax.dynamic_update_slice(stage[0], ks, (0, 0, 0, 0, 0)),
        jax.lax.dynamic_update_slice(stage[1], vs, (0, 0, 0, 0, 0)),
    )


def nns_adopt_scatter(leaf, ids, vals):
    return leaf.at[:, ids].set(vals)


def _prefill_programs(family, weights, windowed: bool):
    """The prompt programs, the family's own (the dense family's are
    dec.prefill / dec.verify_chunk, as ever): one bucket from position 0,
    one bucket at ``cpos`` against a staging cache with and without the
    vocab head, — on a windowed batcher, None elsewhere — the two exact
    sliding-window ring chunks for prompts of ANY length in the fixed W
    ring, and — where the family has one, None elsewhere — the bucket that
    holds several prompts (``family.prefill_packed``)."""
    def wjit(fn, **kw):
        return _weights_jit(fn, weights, **kw)

    packed = None
    if hasattr(family, "prefill_packed"):
        packed = wjit(
            lambda w, toks, positions, segment, last: family.prefill_packed(
                w[0], toks, positions, segment, last
            ),
            name="nns_prefill_packed",
        )

    prefill = wjit(
        lambda w, toks: family.prefill(w[0], toks), name="nns_prefill",
    )
    chunk = wjit(
        lambda w, toks, cpos, cache: family.chunk(w[0], toks, cpos, cache),
        donate_argnums=2, name="nns_prefill_chunk",
    )
    advance = wjit(
        lambda w, toks, cpos, cache: family.chunk(
            w[0], toks, cpos, cache, return_logits=False,
        )[1],
        donate_argnums=2, name="nns_prefill_chunk_nologits",
    )
    if not windowed:
        return prefill, chunk, advance, None, None, packed
    ring_chunk = wjit(
        lambda w, toks, cpos, n, cache: dec.windowed_chunk(
            w[0], toks, cpos, n, cache, family.n_heads,
            compute_dtype=family.compute_dtype,
        )[:2],
        donate_argnums=3, name="nns_prefill_ring",
    )
    ring_advance = wjit(
        lambda w, toks, cpos, n, cache: dec.windowed_chunk(
            w[0], toks, cpos, n, cache, family.n_heads,
            compute_dtype=family.compute_dtype, return_logits=False,
        )[1],
        donate_argnums=3, name="nns_prefill_ring_nologits",
    )
    return prefill, chunk, advance, ring_chunk, ring_advance, packed


# ---- the decode programs: one body, three builders, over a cache layout ----
#
# A *layout* is where the KV cache lives and how a forward reads and writes
# it; it is all that differs between ``kv_layout="slot"`` and ``"paged"``.
# In a program it answers ``forward`` / ``verify`` / ``propose`` over
# ``carried`` (the cache pytree the program carries and DONATES) and
# ``fixed`` (what it only reads, never donated); on the host it hands the
# batcher ``b`` those two (``args``), takes the carried tree back
# (``commit``), advances queued prompts before a launch
# (``advance_prefill``) and makes room for the tokens the launch may write
# (``ensure_room``). Every decode program has the signature
# ``(w, …per-slot vectors…, carried, hist, …, *fixed, <static sizes>)``
# whatever the layout, so the entry points build one argument tuple and
# unpack one result.


def decode_token(logits, tok, pos2, active, hist, budget, stop, temp, topk,
                 topp, keys, sampling: bool, wrap: bool):
    """What one decoded token does to the per-slot state, after the
    forward: pick it, keep idle lanes, record it, spend budget, stop.

    logits [B, V] at fill ``pos2`` [B] → (tok' [B], emit [B] with -1 on
    idle lanes, hist', budget', active'). ``sampling`` is static: the
    greedy-only program compiles without the filtering/PRNG work."""
    with jax.named_scope("nns.sample"):
        if sampling:
            # per-slot key = fold_in(base, fill level): token streams are
            # deterministic per (seed, position), independent of batch
            # composition
            sub = jax.vmap(jax.random.fold_in)(keys, pos2)
            new = sample_tokens(logits, temp, topk, topp, sub)
        else:
            new = jnp.argmax(logits, -1).astype(jnp.int32)
    new = jnp.where(active, new, tok)
    emit = jnp.where(active, new, -1)
    hist = hist_write_row(
        hist, new[:, None], pos2, active.astype(jnp.int32), wrap=wrap
    )
    budget = budget - active.astype(jnp.int32)
    active = active & (budget > 0) & ~((new == stop) & (stop >= 0))
    return new, emit, hist, budget, active


class _Layout:
    """What the two layouts share: prompt-lookup proposals off the device
    history, and a host side with nothing to do."""

    windowed = False

    def propose(self, w, tok, pos, active, carried, hist, k: int, g: int):
        """k-1 proposals per slot for one speculative round →
        (props [B, k-1], carried')."""
        return device_ngram_propose(
            hist, pos, k, g, wrap=self.windowed
        ), carried

    def shard(self, impl):
        return impl

    def advance_prefill(self, b) -> None:
        pass

    def ensure_room(self, b, n: int) -> None:
        pass

    def live_blocks(self, b) -> int:
        return 0

    def state_bytes(self, b) -> int:
        return 0


class _SlotLayout(_Layout):
    """One contiguous ``[L, n_slots, max_len, KV, Dh]`` cache per K and V
    (int8 payload + scale pairs when quantized), every slot sized for the
    worst case; a ring over the last ``max_len`` tokens when ``windowed``.
    Carries ``(cache, draft cache or None)``: where there is a draft model
    its cache steps in lock-step inside the same programs."""

    def __init__(self, family, n_slots: int, max_len: int, quantized: bool,
                 windowed: bool = False, attn_fn=None,
                 draft_n_heads: Optional[int] = None, mesh=None,
                 slots_axis: str = "dp"):
        self.family = family
        self.windowed = windowed
        self.attn_fn = attn_fn
        self.draft_n_heads = draft_n_heads  # None: no draft model
        self.mesh, self.slots_axis = mesh, slots_axis
        self.quantized = quantized
        self.shape = (family.n_layers, n_slots, max_len, family.n_kv_heads,
                      family.head_dim)
        self.ring_shape = self.shape[:1] + (1,) + self.shape[2:]

    def init_cache(self):
        if self.quantized:
            return tuple(
                (jnp.zeros(self.shape, jnp.int8),
                 jnp.ones(self.shape[:-1], jnp.float32))
                for _ in range(2)
            )
        dt = self.family.compute_dtype
        return jnp.zeros(self.shape, dt), jnp.zeros(self.shape, dt)

    def _step(self, params, n_heads, tok, pos, active, cache, attn_fn=None,
              windowed=False):
        return batched_decode_step(
            params, tok, pos, active, cache, n_heads,
            self.family.compute_dtype, attn_fn=attn_fn, windowed=windowed,
        )

    def forward(self, w, tok, pos, active, carried, fixed):
        cache, dcache = carried
        if self.draft_n_heads is not None:
            # the draft ingests the pending token's K/V in lockstep with
            # the target: a hole at a plainly decoded position would have
            # every later propose() condition on garbage K/V there, and
            # acceptance would silently collapse for the rest of the
            # generation
            _, dcache, _ = self._step(
                w[1], self.draft_n_heads, tok, pos, active, dcache,
                windowed=self.windowed,
            )
        logits, cache, pos2 = self._step(
            w[0], self.family.n_heads, tok, pos, active, cache,
            self.attn_fn, self.windowed,
        )
        return logits, (cache, dcache), pos2, None

    def verify(self, w, toks, pos, active, carried, fixed):
        """Score the chunks → (logits [B, k, V], commit); ``commit(m)``
        gives the carried cache once the accepted counts are known (a ring
        lands only accepted columns, so rejected proposals never clobber
        window history; a linear cache was written by the forward)."""
        cache, dcache = carried
        f = self.family
        if self.windowed:
            logits, cks, cvs = batched_windowed_verify(
                w[0], toks, pos, active, cache, f.n_heads, f.compute_dtype
            )
            return logits, lambda m: (
                commit_ring_chunk(cache, cks, cvs, pos, m, active), dcache
            )
        logits, cache = batched_verify_step(
            w[0], toks, pos, active, cache, f.n_heads, f.compute_dtype
        )
        return logits, lambda m: (cache, dcache)

    def propose(self, w, tok, pos, active, carried, hist, k: int, g: int):
        if self.draft_n_heads is None or self.windowed:
            return super().propose(w, tok, pos, active, carried, hist, k, g)
        # k greedy draft steps: k-1 proposals + the k-th write (the
        # full-acceptance K/V invariant, _DraftEngine.propose)
        cache, dc = carried
        cur, p, outs = tok, pos, []
        for _ in range(k):
            dlg, dc, p = self._step(
                w[1], self.draft_n_heads, cur, p, active, dc
            )
            cur = jnp.argmax(dlg, -1).astype(jnp.int32)
            outs.append(cur)
        return jnp.stack(outs[: k - 1], axis=1), (cache, dc)

    def shard(self, impl):
        """The pump over a slot-sharded mesh with the kernel inline. GSPMD
        cannot partition the kernel's custom call over the slot-sharded
        cache — but the scan is slot-parallel by construction, so
        shard_map IS the partition: each device pumps its local slots."""
        if self.mesh is None or self.attn_fn is None:
            return impl
        vec, cac = P(self.slots_axis), P(None, self.slots_axis)

        def sharded(w, tok, pos, active, carried, hist, budget, stop, temp,
                    topk, topp, keys, n_steps):
            return jax.shard_map(
                functools.partial(impl, n_steps=n_steps), mesh=self.mesh,
                # the weights (first) stay replicated on every device
                in_specs=(P(), vec, vec, vec, (cac, cac)) + (vec,) * 7,
                out_specs=(vec, vec, vec, vec, (cac, cac), vec, vec),
                check_vma=False,
            )(w, tok, pos, active, carried, hist, budget, stop, temp, topk,
              topp, keys)

        return sharded

    def args(self, b):
        draft = b._draft._cache if b._draft is not None else None
        return (b._cache, draft), ()

    def commit(self, b, carried) -> None:
        b._cache, dcache = carried
        if b._draft is not None:
            b._draft._cache = dcache


class _PagedLayout(_Layout):
    """The block arena behind per-slot block tables (nnstreamer_tpu/kv/):
    the forward is the family's, straight off the arena through the
    tables; the arena is carried and donated, the tables are only read (the
    cached device copy is reused across pumps)."""

    def __init__(self, family, attn_fn=None):
        self.family = family
        self.attn_fn = attn_fn

    def forward(self, w, tok, pos, active, arena, fixed):
        return self.family.decode_step(
            w[0], tok, pos, active, arena, fixed[0], attn_fn=self.attn_fn
        )

    def verify(self, w, toks, pos, active, arena, fixed):
        # inline XLA attention, like the slot layout's verify
        f = self.family
        logits, arena = kvb.batched_verify_step_block(
            w[0], toks, pos, active, arena, fixed[0], f.n_heads,
            f.compute_dtype,
        )
        return logits, lambda m: arena

    def args(self, b):
        return b._cache, (b._tables_device_locked(),)

    def commit(self, b, arena) -> None:
        b._cache = arena

    def advance_prefill(self, b) -> None:
        b._advance_prefill()

    def ensure_room(self, b, n: int) -> None:
        b._ensure_decode_room_locked(n)

    def live_blocks(self, b) -> int:
        return b._live_blocks_locked()

    def state_bytes(self, b) -> int:
        """Per-slot state (the family's slot leaves) of the live lanes."""
        return int(b._active.sum()) * b._slot_state_bytes


# carried and hist, counted with the weights first: aliased outputs update
# in place, so the carried state never has two live copies
_DONATE = (4, 5)


def make_pump(layout, sampling: bool):
    """``n_steps`` tokens per program launch: ``lax.scan`` carries
    (tok, pos, active, cache, hist, budget) on device, deactivates slots at
    budget/stop-token inside the scan, and emits -1 for idle lanes — one
    dispatch and ONE readback per pump, ``[B, n]`` tokens (flattened, with
    the family's counters summed over the steps behind them, where the
    family has any). Budget and the active mask ride back out so the host
    carries them on device across pumps instead of re-shipping its state.
    Jitted with the weights as first argument."""

    def impl(w, tok, pos, active, carried, hist, budget, stop, temp, topk,
             topp, keys, *fixed, n_steps):
        def body(c, _):
            tok, pos, active, carried, hist, budget = c
            logits, carried, pos2, aux = layout.forward(
                w, tok, pos, active, carried, fixed
            )
            new, emit, hist, budget, active = decode_token(
                logits, tok, pos2, active, hist, budget, stop, temp, topk,
                topp, keys, sampling, layout.windowed,
            )
            return (new, pos2, active, carried, hist, budget), (emit, aux)

        c, (emits, aux) = jax.lax.scan(
            body, (tok, pos, active, carried, hist, budget), None,
            length=n_steps,
        )
        emits = emits.T
        if aux is not None:  # the family's counters ride the one readback
            emits = jnp.concatenate(
                [emits.reshape(-1), jnp.sum(aux, axis=0)]
            )
        return (emits,) + c

    # the module must stay ``jit_impl`` on a device trace: benchmark/configs/
    # *.json select the decode launches by that name (trace_names.decode)
    # and decode_step_ms, decode_hbm_roofline_pct, step_mfu_pct and
    # prefill_ms_per_pump divide by their count. Renaming it is a benchmark
    # PR's (ROADMAP S8).
    return jax.jit(
        _named(layout.shard(impl), "impl"), donate_argnums=_DONATE,
        static_argnames=("n_steps",),
    )


def _spec_round(layout, w, toks, pos, active, carried, fixed, hist, temp,
                topk, topp, keys, sampling: bool):
    """One speculative round = verify + device-side acceptance (+ the
    layout's commit of accepted columns) + the emitted row into the
    history. Only [B] m-counts and [B] final tokens are its results —
    never [B, k, V] logits (sampling acceptance needs the full
    distributions, which at a 32k+ vocab must not ship per round)."""
    logits, commit = layout.verify(w, toks, pos, active, carried, fixed)
    m, final = spec_accept(
        logits, toks, temp, topk, topp, keys, pos, sampling
    )
    m = jnp.where(active, m, 0)
    carried = commit(m)
    emit, hist = spec_emit_hist(
        toks, m, final, active, hist, pos, layout.windowed
    )
    return m, final, carried, hist, pos + m, emit


def make_spec_round(layout, sampling: bool):
    """The host-proposed round (spec_step); jit caches one program per
    distinct chunk width."""

    def impl(w, toks, pos, active, carried, hist, temp, topk, topp, keys,
             *fixed):
        return _spec_round(
            layout, w, toks, pos, active, carried, fixed, hist, temp, topk,
            topp, keys, sampling,
        )[:5]

    return jax.jit(impl, donate_argnums=_DONATE)


def make_spec_pump(layout, sampling: bool):
    """``rounds`` whole propose→verify→accept→commit rounds per program
    launch (proposals from the layout: the device history's n-grams, or an
    in-scan draft model), shipping ONE packed int32 vector back:
    [B·R·k emitted tokens ‖ accepted-count ‖ proposal-columns]. Acceptance
    telemetry therefore costs no extra transfer."""

    def impl(w, tok, pos, active, carried, hist, budget, stop, temp, topk,
             topp, keys, *fixed, rounds, k, g):
        def body(c, _):
            tok, pos, active, carried, hist, budget, acc, cols = c
            props, carried = layout.propose(
                w, tok, pos, active, carried, hist, k, g
            )
            props = jnp.where(active[:, None], props, -1)
            toks = jnp.concatenate([tok[:, None], props], axis=1)
            m, final, carried, hist, pos2, emit = _spec_round(
                layout, w, toks, pos, active, carried, fixed, hist, temp,
                topk, topp, keys, sampling,
            )
            acc = acc + jnp.sum(jnp.maximum(m - 1, 0))
            cols = cols + jnp.sum((props >= 0).astype(jnp.int32))
            budget = budget - m
            hit_stop = jnp.any(
                (emit == stop[:, None]) & (stop[:, None] >= 0), axis=1
            )
            active = active & (budget > 0) & ~hit_stop
            tok = jnp.where(m > 0, final, tok)
            return (tok, pos2, active, carried, hist, budget, acc,
                    cols), emit

        zero = jnp.zeros((), jnp.int32)
        c, emits = jax.lax.scan(
            body, (tok, pos, active, carried, hist, budget, zero, zero),
            None, length=rounds,
        )
        packed = jnp.concatenate([
            jnp.transpose(emits, (1, 0, 2)).reshape(-1), jnp.stack(c[6:]),
        ])
        return (packed,) + c[:6]

    return jax.jit(
        impl, donate_argnums=_DONATE, static_argnames=("rounds", "k", "g"),
    )


class _DraftEngine:
    """Batched draft-model proposer for spec_step: ONE small model
    stepping ALL active slots greedily k-1 times per round, with its own
    slot cache mirroring the target's per-slot positions — draft-model
    speculation at serving scale (the single-stream analogue is
    models/speculative.speculative_generate; the acceptance logic is the
    shared spec_accept, since a greedy draft is a point-mass proposer
    exactly like prompt lookup).

    Rollback is positional, like the target's: after a round the caller
    resumes from the target's accepted pos. On a LINEAR cache the draft
    writes while proposing — accepted positions hold its own proposals,
    rejected ones are overwritten before any mask reaches them. On a
    WINDOWED ring that invariant fails (rejected writes would clobber
    live window history), so the draft uses the same verify-then-commit
    discipline as the target: draft_windowed_propose runs the whole
    chain against the pre-write ring plus its own fresh chunk, and
    commit() lands only the accepted columns after the target rules."""

    def __init__(self, params, n_heads, n_slots, max_len, prompt_len,
                 compute_dtype, windowed: bool = False):
        self.params = params
        self.n_heads = n_heads
        self.prompt_len = prompt_len
        self.max_len = max_len
        self.compute_dtype = compute_dtype
        self.windowed = windowed
        L, d = params["blocks"]["ln1"].shape
        hd = d // n_heads
        kv = tfm.n_kv_heads_of(params["blocks"]["wqkv"], d, n_heads)
        self._cache = (
            jnp.zeros((L, n_slots, max_len, kv, hd), compute_dtype),
            jnp.zeros((L, n_slots, max_len, kv, hd), compute_dtype),
        )
        stage_len = (-(-max_len // prompt_len) + 1) * prompt_len
        self._stage_shape = (L, 1, stage_len, kv, hd)
        self._ring_shape = (L, 1, max_len, kv, hd)
        self._advance = _weights_jit(
            lambda w, toks, cpos, cache: dec.verify_chunk(
                w, toks, cpos, cache, n_heads,
                compute_dtype=compute_dtype, return_logits=False,
            )[1],
            params, donate_argnums=2, name="nns_draft_prefill_chunk",
        )
        self._wadvance = _weights_jit(
            lambda w, toks, cpos, n, cache: dec.windowed_chunk(
                w, toks, cpos, n, cache, n_heads,
                compute_dtype=compute_dtype, return_logits=False,
            )[1],
            params, donate_argnums=3, name="nns_draft_prefill_ring",
        )
        self._insert = jax.jit(insert_slot, donate_argnums=0)
        self._propose_w = _weights_jit(
            lambda w, tok, pos, cache, k: draft_windowed_propose(
                w, tok, pos, cache, n_heads, k,
                compute_dtype=compute_dtype,
            ),
            params, static_argnames=("k",), name="nns_draft_propose",
        )
        self._commit_w = jax.jit(commit_ring_chunk, donate_argnums=0)
        self._pending_chunk = None  # windowed: (cks, cvs) awaiting commit

        def step(w, tok, pos, active, cache):
            logits, cache, pos2 = batched_decode_step(
                w, tok, pos, active, cache, n_heads, compute_dtype,
                windowed=windowed,
            )
            return jnp.argmax(logits, -1).astype(jnp.int32), cache, pos2

        self._step = _weights_jit(step, params, donate_argnums=3)

    def prefill_tokens(self, tokens: np.ndarray):
        """Draft-prefill a request's FULL context (prefix + prompt) in
        prompt_len buckets → (ks, vs) [L, 1, max_len, KV, Dh] ready for
        insert_slot (a W-ring in windowed mode — same shape). No
        logits: the first pending token is the target's, the draft only
        ever continues from certified tokens."""
        P = self.prompt_len
        t = tokens.shape[0]
        if self.windowed:
            ring = (
                jnp.zeros(self._ring_shape, self.compute_dtype),
                jnp.zeros(self._ring_shape, self.compute_dtype),
            )
            cpos = 0
            while cpos < t:
                n = min(P, t - cpos)
                chunk = np.zeros((1, P), np.int32)
                chunk[0, :n] = tokens[cpos : cpos + n]
                ring = self._wadvance(
                    jnp.asarray(chunk), jnp.asarray(cpos, jnp.int32),
                    jnp.asarray(n, jnp.int32), ring,
                )
                cpos += n
            return ring
        stage = (
            jnp.zeros(self._stage_shape, self.compute_dtype),
            jnp.zeros(self._stage_shape, self.compute_dtype),
        )
        cpos = 0
        while cpos < t:
            n = min(P, t - cpos)
            chunk = np.zeros((1, P), np.int32)
            chunk[0, :n] = tokens[cpos : cpos + n]
            stage = self._advance(
                jnp.asarray(chunk), jnp.asarray(cpos, jnp.int32), stage
            )
            cpos += n
        return stage[0][:, :, : self.max_len], stage[1][:, :, : self.max_len]

    def admit(self, slot: int, draft_kv) -> None:
        self._cache = self._insert(self._cache, *draft_kv, slot)

    def commit(self, pos, m, active) -> None:
        """Windowed only: land the accepted columns of the last
        propose()'s chunk into the draft ring (the draft-side half of
        the verify-then-commit discipline)."""
        if self._pending_chunk is None:
            return
        cks, cvs = self._pending_chunk
        self._pending_chunk = None
        self._cache = self._commit_w(self._cache, cks, cvs, pos, m, active)

    def propose(self, tok, pos, active, k: int) -> np.ndarray:
        """k-1 greedy draft proposals per slot [B, k-1] (np).

        Linear cache: k sequential batched steps writing in place (the
        k-th emission is discarded — that step exists for its WRITE: on
        full acceptance the last proposal's K/V must be in the cache at
        pos+k-1 or the next round would attend an unwritten hole, the
        single-stream _draft_k invariant). Windowed ring: one
        draft_windowed_propose program against the pre-write ring; its
        chunk K/V parks in _pending_chunk until commit()."""
        if self.windowed:
            props, cks, cvs = self._propose_w(tok, pos, self._cache, k=k)
            self._pending_chunk = (cks, cvs)
            return np.asarray(props)
        cache = self._cache
        cur, p = tok, pos
        props = []
        for _ in range(k):
            cur, cache, p = self._step(cur, p, active, cache)
            props.append(cur)
        self._cache = cache
        return np.stack([np.asarray(c) for c in props[: k - 1]], axis=1)


class BatcherFailedError(RuntimeError):
    """The batcher's device state is invalid: a step/pump launch raised
    AFTER dispatch, so the donated ``_cache``/``_hist`` (and draft cache)
    buffers were consumed while the attributes still reference them.
    Every later call would hit a cryptic deleted-buffer error; this typed
    error names the original failure instead. Build a new batcher."""


class ContinuousBatcher:
    """Continuous-batching server over a fixed slot batch (greedy by
    default; per-request temperature/top-k/top-p sampling via submit()).

    submit() may be called at any time (thread-safe); step() advances every
    active slot by one token. Finished requests free their slot for the
    next submit — the batch never drains to admit new work.

    Failure semantics: the step/pump programs donate the KV cache, so a
    raise after dispatch poisons the carried state irreversibly. The
    batcher marks itself failed (``_mark_failed``) and every subsequent
    step/pump/submit raises :class:`BatcherFailedError` chained to the
    original exception — mirroring submit()'s slot-release rollback.
    """

    def __init__(
        self,
        params: Dict,
        n_heads: int,
        n_slots: int = 4,
        max_len: int = 256,
        prompt_len: int = 64,
        compute_dtype=jnp.float32,
        attn_impl: str = "",
        keep_results: int = 1024,
        cache_dtype: str = "auto",
        mesh=None,
        slots_axis: str = "dp",
        windowed: bool = False,
        draft_params: Optional[Dict] = None,
        draft_n_heads: Optional[int] = None,
        kv_layout: str = "slot",
        block_size: int = 16,
        kv_blocks: Optional[int] = None,
        prefill_chunks: int = 0,
        family=None,
    ):
        """``windowed=True`` makes max_len a sliding attention window
        over a ring-buffer cache: generations AND prompts of any length
        run in the fixed [max_len] cache, each token attending the
        previous max_len (Mistral-style sliding-window attention — the
        time-axis sibling of tensor_aggregator's bounded windows).

        ``attn_impl`` picks the decode attention: ``"xla"``,
        ``"pallas"`` (the decode kernels of ops/pallas), or unset
        (``""``): the block-table kernel under the paged layout on a
        TPU backend where the registry passes the arena
        dtype — the rule of ``kv.block_attn.block_attention
        (impl="auto")`` — and ``"xla"`` everywhere else, so off-TPU the
        default stays the bit-pinned XLA formulation. ``stats()``
        reports what was resolved as ``attn_impl``.

        The full feature matrix composes: attn_impl="pallas" works with
        cache_dtype="int8" (the kernel takes the scale operands and
        dequantizes in VMEM), with mesh= (the pump is wrapped in
        shard_map over the slot axis, so each device runs the kernel on
        its local slots), and with windowed=True.

        ``draft_params`` plugs a DRAFT MODEL into spec_step: instead of
        prompt-lookup, a small model proposes k-1 tokens per slot per
        round (k-1 cheap batched forwards), verified by the same chunked
        target forward and accepted by the same point-mass logic — the
        serving-scale form of models/speculative.speculative_generate.
        The draft must share the target's vocabulary. Composes with
        windowed rings: the draft proposes against its pre-write ring
        and commits only accepted columns — the same verify-then-commit
        discipline the target uses (see _DraftEngine).

        ``kv_layout="paged"`` keeps the cache as a block arena behind
        per-slot block tables (docs/llm-serving.md): decode attends the
        arena through the tables and writes each token in place into its
        owning block (kv/block_attn.py), bitwise identical to the slot
        layout; ``attn_impl="pallas"`` there is the block-table kernel
        (ops/pallas/paged_attention.py).

        ``family`` is the block family the paged path serves
        (models/family.py): what a token leaves in the cache and how a
        step computes. Unset is the dense block of models/transformer.py
        (``params`` + ``n_heads``); another family brings its own arena
        leaves, prefill and decode programs, and refuses by name what it
        does not carry."""
        ensure_compile_cache()
        if family is None:
            family = DenseFamily(params, n_heads, prompt_len, compute_dtype)
        refuse_unsupported(family, {
            "kv-layout=slot": kv_layout != "paged",
            "cache-dtype=int8": cache_dtype == "int8",
            "windowed": windowed,
            "mesh": mesh is not None,
            "draft model": draft_params is not None,
        })
        self._family = family
        if prompt_len > max_len:
            raise ValueError("prompt_len must be ≤ max_len")
        if cache_dtype not in ("auto", "int8"):
            raise ValueError(f"unknown cache_dtype {cache_dtype!r}")
        quantized_cache = cache_dtype == "int8"
        if kv_layout not in ("slot", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if attn_impl not in ("", "xla", "pallas"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        self._paged = kv_layout == "paged"
        if self._paged:
            # paged KV (nnstreamer_tpu/kv/, docs/llm-serving.md): the
            # cache is a block arena behind per-slot block tables, which
            # decode attends and writes in place (kv/block_attn.py),
            # bitwise identical to the slot layout (tests/test_kv_paged.py,
            # tests/test_kv_block_attn.py). The windowed ring,
            # slot-sharded meshes and draft models keep the slot layout
            # for now.
            for flag, why in (
                (windowed, "windowed (ring) caches"),
                (mesh is not None, "mesh-sharded slots"),
                (draft_params is not None, "draft models"),
            ):
                if flag:
                    raise ValueError(
                        f"kv_layout='paged' does not support {why}; "
                        "use the slot layout"
                    )
            block_size = int(block_size)
            if block_size < 1 or max_len % block_size:
                raise ValueError(
                    f"block_size({block_size}) must divide "
                    f"max_len({max_len})"
                )
            if prompt_len % block_size:
                raise ValueError(
                    f"block_size({block_size}) must divide "
                    f"prompt_len({prompt_len}) so staged prefill chunks "
                    "land on block boundaries"
                )
        if not attn_impl:
            # unset: the block-table kernel where it is the measured
            # fast path (PERF.md, PR 26) — paged decode on a TPU backend —
            # subject to the registry gate below; the XLA formulation
            # everywhere else
            attn_impl = (
                "pallas"
                if self._paged and jax.default_backend() == "tpu"
                and family.decode_kernel is not None
                else "xla"
            )
        if attn_impl == "pallas":
            # registry dtype/env gate (_compat.pallas_ok): a request the
            # kernels can't serve degrades to the XLA step with a logged
            # reason instead of a trace-time error mid-construction
            from nnstreamer_tpu.ops.pallas._compat import pallas_ok

            kernel = (
                family.decode_kernel if self._paged
                else "decode_attention"
            )
            ok = kernel is not None and pallas_ok(
                kernel, "int8" if quantized_cache else family.dtype
            )[0]
            if not ok:
                attn_impl = "xla"
        _record_dispatch("serving_attention", attn_impl)
        attn_fn = None
        if attn_impl == "pallas" and self._paged:
            # the block-table kernel: attends the arena through the
            # prefetched tables, one block per grid step
            # (ops/pallas/paged_attention.py); the spec verify keeps
            # inline XLA attention exactly like the slot layout's Pallas
            # batchers
            attn_fn = family.make_attention()
        elif attn_impl == "pallas":
            from nnstreamer_tpu.ops.pallas.decode_attention import (
                make_decode_attention,
            )

            attn_fn = make_decode_attention()
        self.params = params
        self.n_heads = n_heads
        self.n_slots = n_slots
        self.max_len = max_len
        self.windowed = windowed
        self._attn_impl = attn_impl
        self.prompt_len = prompt_len
        self.compute_dtype = compute_dtype
        self._lock = threading.Lock()       # host/device state
        self._step_lock = threading.Lock()  # serializes device steps
        # set by _mark_failed when a donated-state launch raised after
        # dispatch; read lock-free (GIL-atomic) by _check_failed
        self._failed: Optional[Exception] = None
        self._next_rid = 0
        self._slots: List[Optional[_Request]] = [None] * n_slots
        self._pending: List[_PendingInsert] = []
        # finished requests await pickup here; bounded FIFO so a caller
        # that never collects cannot grow the host heap without limit
        self._done_pool: "OrderedDict[int, _Request]" = OrderedDict()
        self._keep_results = keep_results

        # nns-obs: the SLO histograms + paged-pool gauges emit through
        # the registry resolved ONCE here (the FaultGate discipline)
        self._obs_reg = _obs_metrics.get()
        self._slo = SLOLedger(keep=keep_results, obs_registry=self._obs_reg)
        # the last harvest that left a slot decoding (perf_counter), for
        # nns.pump.harvest's gap_ms and nns_decode_gap_ms
        self._harvest_t: Optional[float] = None
        self._gap_hist = (
            self._obs_reg.histogram("nns_decode_gap_ms")
            if self._obs_reg is not None else None
        )

        self._draft = (
            _DraftEngine(
                draft_params, draft_n_heads or n_heads, n_slots, max_len,
                prompt_len, compute_dtype, windowed=windowed,
            )
            if draft_params is not None else None
        )
        # chunked-prefill jobs waiting for their next bucket (paged; the
        # slot layout prefills inside submit and queues nothing)
        self._prefill_q: deque = deque()
        if self._paged:
            self._layout = _PagedLayout(family, attn_fn)
            self.block_size = block_size
            self._blocks_per_slot = max_len // block_size
            if kv_blocks is None:
                # no-saving default: enough blocks for every slot at
                # max_len — memory savings come from setting kv_blocks
                # BELOW this (the bench's fixed-HBM-budget cell)
                kv_blocks = n_slots * self._blocks_per_slot
            if kv_blocks < self._blocks_per_slot:
                raise ValueError(
                    f"kv_blocks({kv_blocks}) cannot hold even one "
                    f"max_len request ({self._blocks_per_slot} blocks)"
                )
            # a family that cannot share prefixes (per-slot state: the
            # state at a block boundary is stored nowhere) indexes no
            # block, so no prompt ever matches one
            self._pool = BlockPool(
                int(kv_blocks), block_size, obs_registry=self._obs_reg,
                index="prefix sharing" not in family.unsupported,
            )
            # self._cache IS the arena in paged mode (block leaves, then
            # the family's slot leaves, models/family.py): every
            # donated-launch/commit/failure-latch path stays identical
            self._cache = family.arena(
                int(kv_blocks), block_size, quantized_cache, n_slots
            )
            # resident bytes of one slot's row over the slot leaves
            self._slot_state_bytes = sum(
                leaf.nbytes // leaf.shape[1]
                for leaf in self._cache[len(self._cache) - family.slot_leaves:]
            )
            if self._obs_reg is not None and family.slot_leaves:
                self._obs_reg.gauge("nns_slot_state_bytes").set(
                    float(self._slot_state_bytes * n_slots)
                )
            self._tables = np.zeros(
                (n_slots, self._blocks_per_slot), np.int32
            )
            self._n_alloc = np.zeros((n_slots,), np.int32)
            self._tables_dev = jnp.asarray(self._tables)
            self._tables_dirty = False
            self._write_block, self._read_block, self._copy_block = (
                kvg.make_paged_ops(quantized_cache, compute_dtype)
            )
            # coalesced admission staging (kv/gather.make_staging_ops):
            # prefix seeding and block landing as ONE program each —
            # the per-block read/write launches used to dominate paged
            # admission latency on short decode budgets
            self._seed_stage, self._land_stage = kvg.make_staging_ops(
                quantized_cache, compute_dtype
            )
            # live migration (kv/migrate.py): raw per-leaf block scatter
            # — donated like every other arena mutator, and bypassing
            # the quantize/dequantize in write_block/read_block so an
            # int8 span lands the exact bytes the source held
            self._adopt_scatter = jax.jit(nns_adopt_scatter, donate_argnums=0)
            self._quantized = quantized_cache
            self._n_migrations_out = 0
            self._n_migrations_in = 0
            self._n_resumes = 0
            self._n_prefill_chunk_programs = 0
            self._n_prefill_pumps = 0
            # prompt programs of any kind, the prompts they completed, and
            # the programs among them that held a bin of prompts
            self._n_prefill_programs = 0
            self._n_prefill_prompts = 0
            self._n_prefill_packed_programs = 0
            # bucket programs a pump may spend on the prefill queue:
            # 0 = as many as jobs are queued at its start, N = at most N
            self._prefill_chunks = max(0, int(prefill_chunks))
            self._prefixes_paged: Dict[int, Tuple[np.ndarray, List[int]]] = {}
        else:
            self._layout = _SlotLayout(
                family, n_slots, max_len, quantized_cache, windowed, attn_fn,
                self._draft.n_heads if self._draft is not None else None,
                mesh, slots_axis,
            )
            self._pool = None
            self._cache = self._layout.init_cache()
        self._tok = jnp.zeros((n_slots,), jnp.int32)
        self._pos = jnp.zeros((n_slots,), jnp.int32)
        self._active = np.zeros((n_slots,), bool)
        # per-slot sampling state lives ON DEVICE so the step program
        # samples in place (host sees one token id per slot per step)
        self._temp = jnp.zeros((n_slots,), jnp.float32)
        self._topk = jnp.zeros((n_slots,), jnp.int32)
        self._topp = jnp.ones((n_slots,), jnp.float32)
        self._keys = jnp.zeros((n_slots, 2), jnp.uint32)
        # per-slot token history ON DEVICE (-1 padded): the n-gram
        # mining context for device-side prompt-lookup speculation and
        # the multi-step pumps' running record — tokens never have to
        # come back to the host just to propose continuations
        self._hist = jnp.full((n_slots, max_len), -1, jnp.int32)
        # device-carried pump state: remaining budgets, stop ids and the
        # active mask live ON DEVICE between pumps (the scan already
        # computes their next values — they used to be recomputed and
        # re-shipped from host EVERY pump even when no slot changed).
        # _pump_state_locked() rebuilds + ships them only when the dirty
        # flag says admission/finish/host-stepping touched a slot; a
        # steady pump-only drain performs ZERO host-state H2D transfers
        # (pinned in tests/test_pumps.py beside the no-new-compiles
        # regression test).
        self._budget_dev = jnp.zeros((n_slots,), jnp.int32)
        self._stop_dev = jnp.full((n_slots,), -1, jnp.int32)
        self._active_dev = jnp.zeros((n_slots,), bool)
        self._pump_state_dirty = True
        self._host_state_builds = 0  # regression-test observable

        # every program that runs a model takes the weights as its
        # first ARGUMENT (_weights_jit) — (target, draft) — never as a
        # closed-over constant
        weights = (params, draft_params)
        self._vec_sh = None
        if mesh is not None:
            # shard the slot axis over the mesh: the batched step runs
            # SPMD with each device decoding its share of the slots (the
            # data-parallel serving layout; params stay replicated, so
            # the only cross-device traffic is the host-driven admit)
            n_mesh = mesh.shape[slots_axis]
            if n_slots % n_mesh:
                raise ValueError(
                    f"n_slots={n_slots} must divide over mesh axis "
                    f"{slots_axis!r} (size {n_mesh})"
                )
            cache_sh = NamedSharding(mesh, P(None, slots_axis))
            self._vec_sh = batch_sharding(mesh, slots_axis)
            self._cache = jax.tree_util.tree_map(
                lambda c: jax.device_put(c, cache_sh), self._cache
            )
            (self._tok, self._pos, self._temp, self._topk, self._topp,
             self._keys, self._hist) = (
                self._pin(x) for x in (
                    self._tok, self._pos, self._temp, self._topk,
                    self._topp, self._keys, self._hist,
                )
            )
            # replicated over the mesh ONCE, here — an uncommitted
            # pytree would be re-placed on every call
            weights = jax.device_put(weights, NamedSharding(mesh, P()))

        (self._prefill, self._prefill_chunk, self._advance_chunk,
         self._wchunk, self._wadvance, self._prefill_packed) = (
            _prefill_programs(family, weights, windowed)
        )
        # chunked prefill (prompts longer than the bucket) stages into a
        # cache padded to a bucket multiple — plus one spare bucket so
        # chunk starts NOT aligned to the bucket (the prefix-caching
        # path) still fit their full-width writes
        self._stage_len = (-(-max_len // prompt_len) + 1) * prompt_len
        self._sample1 = jax.jit(nns_sample_first)
        self._sample_rows = jax.jit(nns_sample_first_rows)
        self._insert = jax.jit(insert_slot, donate_argnums=0)
        self._admit = _make_admit(max_len, self._vec_sh)
        self._load_prefix = jax.jit(nns_load_prefix, donate_argnums=0)
        # the decode programs: one builder each over the layout, a greedy
        # and a sampling variant (the greedy one compiles without the
        # filtering/PRNG work), the weights bound as first argument
        (self._pump_greedy, self._pump_sampling,
         self._spec_round_greedy, self._spec_round_sampling,
         self._spec_pump_greedy, self._spec_pump_sampling) = (
            functools.partial(make(self._layout, sampling), weights)
            for make in (make_pump, make_spec_round, make_spec_pump)
            for sampling in (False, True)
        )
        # registered shared prefixes:
        # id → ((ck, cv) trimmed to plen, plen, prefix tokens)
        self._prefixes: Dict[
            int, Tuple[Tuple[jax.Array, jax.Array], int, np.ndarray]
        ] = {}
        self._next_prefix = 0
        self._n_steps = 0
        self._n_tokens = 0
        self._n_spec_rounds = 0
        self._n_spec_accepted = 0
        self._n_spec_columns = 0  # proposal columns offered (normalizer)
        self._n_admit_launches = 0  # launches of the admit program
        self._n_admitted = 0  # requests it spliced into the slot state
        self._aux_totals: Dict[str, int] = {}

    def _empty_stage(self):
        return self._family.stage(self._stage_len)

    def _chunk_step(self, tokens, pos: int, stage, want_logits: bool):
        """ONE prompt_len bucket of chunked prefill at absolute ``pos``.
        Every copy of the chunked-prefill invariant (full-width pad
        writes overwritten before masked; verify_chunk's absolute pos;
        the vocab-head projection only when logits are wanted) lives
        HERE — the slot layout's synchronous _stage_chunks and the
        paged incremental job path (_prefill_chunk_one) both drive it.
        Returns (logits or None, advanced stage, tokens consumed)."""
        P = self.prompt_len
        n = min(P, int(tokens.shape[0]))
        chunk = np.full((1, P), self._family.pad_id, np.int32)
        chunk[0, :n] = tokens[:n]
        args = (jnp.asarray(chunk), jnp.asarray(pos, jnp.int32), stage)
        if want_logits:
            logits, stage, _ = self._prefill_chunk(*args)
            return logits, stage, n
        return None, self._advance_chunk(*args), n

    def _stage_chunks(self, tokens, base: int, stage, want_logits: bool):
        """Advance a staging cache with ``tokens`` written at absolute
        positions base..base+t-1, one _chunk_step bucket at a time.
        Returns (final chunk's logits or None, advanced stage)."""
        t = tokens.shape[0]
        cpos = 0
        logits = None
        while cpos < t:
            final = cpos + self.prompt_len >= t
            logits, stage, n = self._chunk_step(
                tokens[cpos:], base + cpos, stage, want_logits and final
            )
            cpos += n
        return logits, stage

    def _stage_ring(self, tokens, base: int = 0, ring=None,
                    want_logits: bool = True):
        """Windowed chunked prefill: advance a W-ring with ``tokens``
        written at absolute positions base..base+t-1, one bucket per
        windowed_chunk call (exact sliding-window attention —
        decode.windowed_chunk). ``ring`` seeds the cache (a registered
        prefix's ring; fresh zeros when None); ``base`` must be a bucket
        multiple (enforced by register_prefix, whose prefix lengths are
        the only nonzero bases) so chunks never wrap mid-write. Returns
        (final chunk's logits or None, ring (ks, vs), last-row index)."""
        # submit()/register_prefix enforce max_len % P == 0 before any
        # chunking reaches here (bucket-sized prefixless prompts never
        # chunk, so unaligned windowed configs stay valid for them)
        P = self.prompt_len
        if ring is None:
            ring = (
                jnp.zeros(self._layout.ring_shape, self.compute_dtype),
                jnp.zeros(self._layout.ring_shape, self.compute_dtype),
            )
        else:
            # the chunk programs DONATE their ring argument — a caller's
            # ring (a registered prefix) must survive this staging run,
            # so advance a fresh copy, never the stored buffers
            ring = (ring[0] + 0, ring[1] + 0)
        t = tokens.shape[0]
        cpos = 0
        logits = None
        while cpos < t:
            n = min(P, t - cpos)
            chunk = np.zeros((1, P), np.int32)
            chunk[0, :n] = tokens[cpos : cpos + n]
            args = (
                jnp.asarray(chunk), jnp.asarray(base + cpos, jnp.int32),
                jnp.asarray(n, jnp.int32), ring,
            )
            if want_logits and cpos + n >= t:
                logits, ring = self._wchunk(*args)
            else:
                ring = self._wadvance(*args)
            cpos += n
        return logits, ring, (t - 1) % P  # last real row of the final chunk

    def register_prefix(self, tokens) -> int:
        """Prefill a shared prompt prefix (e.g. a system prompt) ONCE and
        return its id; submit(prefix=id) starts from its K/V instead of
        re-prefilling it per request — the admission cost of the shared
        part is paid one time. Release with unregister_prefix when no
        longer needed.

        Unwindowed caches store the staged K/V trimmed to the prefix
        length. Windowed caches store the prefix's RING: a prefix always
        starts at absolute position 0, so its ring placement is the same
        for every request — the one alignment requirement is that the
        prefix length be a bucket (prompt_len) multiple, so the
        per-request continuation chunks stay bucket-aligned and never
        wrap the ring mid-write (a windowed prefix may even EXCEED
        max_len: the ring then holds its last W tokens, exactly
        sliding-window semantics)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        plen = tokens.shape[0]
        self._refuse("prefix sharing")
        if self._paged:
            # paged: prefill ONCE into pool blocks, register them in the
            # prefix index, and PIN them (the registration holds one
            # reference until unregister) — later submits hit the index
            # whether or not they pass prefix=; the stored tokens are
            # prepended for prefix= callers so matching sees one stream
            if not (0 < plen < self.max_len):
                raise ValueError(
                    f"prefix length {plen} not in (0, max_len="
                    f"{self.max_len})"
                )
            # _step_lock: the block writes below donate self._cache —
            # they must serialize with in-flight step/pump launches
            # that donate the same arena (submit() stays lock-free
            # because its writes ride the pending queue; registration
            # is setup-time, so the serialization is fine)
            with self._step_lock:
                _, stage = self._stage_chunks(
                    tokens, 0, self._empty_stage(), False
                )
                bs = self.block_size
                n_blocks = -(-plen // bs)
                with self._lock:
                    blocks = self._pool.alloc(n_blocks)
                ids = np.zeros((self._stage_len // bs,), np.int32)
                valid = np.zeros((self._stage_len // bs,), bool)
                ids[: n_blocks] = blocks
                valid[: n_blocks] = True
                self._cache = self._land_stage(
                    self._cache, stage, jnp.asarray(ids),
                    jnp.asarray(valid), np.int32(0),
                )
                with self._lock:
                    self._pool.register(tokens, blocks)
                    pid = self._next_prefix
                    self._next_prefix += 1
                    self._prefixes_paged[pid] = (tokens, blocks)
            return pid
        if self.windowed:
            P = self.prompt_len
            if plen <= 0 or plen % P:
                raise ValueError(
                    f"windowed prefix length {plen} must be a positive "
                    f"multiple of prompt_len({P}) so per-request "
                    "continuation chunks stay bucket-aligned"
                )
            if self.max_len % P:
                raise ValueError(
                    f"windowed prefix caching needs max_len"
                    f"({self.max_len}) to be a multiple of "
                    f"prompt_len({P})"
                )
            _, ring, _ = self._stage_ring(tokens, 0, None, False)
            stored = ring
        else:
            if not (0 < plen < self.max_len):
                raise ValueError(
                    f"prefix length {plen} not in (0, max_len={self.max_len})"
                )
            _, stage = self._stage_chunks(
                tokens, 0, self._empty_stage(), False
            )
            stored = (stage[0][:, :, :plen], stage[1][:, :, :plen])
        with self._lock:
            pid = self._next_prefix
            self._next_prefix += 1
            # tokens ride along so spec_step's prompt-lookup context
            # covers the shared prefix too (proposal quality, not
            # correctness — n-gram matches often live in a system prompt)
            self._prefixes[pid] = (stored, plen, tokens)
        return pid

    def unregister_prefix(self, pid: int) -> bool:
        """Release a registered prefix's device memory (in-flight
        requests are unaffected — their slot cache holds a copy; paged
        sharers hold their own block references, and the blocks stay
        adoptable from the pool's cached tier until reclaimed)."""
        with self._lock:
            if self._paged:
                item = self._prefixes_paged.pop(pid, None)
                if item is None:
                    return False
                self._pool.free(item[1])
                return True
            return self._prefixes.pop(pid, None) is not None

    # -- client API --------------------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: Optional[int] = None,
        stop_token: Optional[int] = None,
        prefix: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> Optional[int]:
        """Claim a free slot for ``prompt`` [T]; returns a request id, or
        None when the batch is full (caller queues/retries — the
        admission queue is the caller's policy, not the batcher's).
        ``deadline_s`` is SLO accounting only (surfaced by requests() /
        nns-top --requests), never an eviction trigger.

        Paged batchers (``kv_layout="paged"``) admit through the chunked
        prefill queue instead of prefilling here: submit returns
        immediately, with no device work and no wait for a launch in
        flight, and the next pumps spend their prefill budget on the
        queue (``_advance_prefill``), interleaved with decode — a long
        prompt cannot stall decoding slots for its whole prefill
        (docs/llm-serving.md).
        Prompts longer than the prompt_len bucket prefill in bucket-sized
        chunks (decode.verify_chunk; decode.windowed_chunk on a ring when
        windowed), so T is bounded by the cache — or by nothing at all
        when windowed (the ring retains the last max_len tokens, exactly
        sliding-window semantics).

        Sampling is per-request: temperature ≤ 0 is greedy; otherwise
        softmax sampling, optionally top-k truncated and/or top-p
        (nucleus) filtered (0 < top_p < 1; the boundary token is kept),
        with a deterministic per-request stream: every token is keyed by
        fold_in(PRNGKey(seed), fill-level), so the stream depends only on
        (seed, position) — never on batch composition."""
        self._check_failed()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        t = prompt.shape[0]
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be ≥ 1, got {max_new_tokens}")
        if t == 0:
            raise ValueError("empty prompt")
        if self._paged:
            return self._submit_paged(
                prompt, max_new_tokens, temperature, top_k, top_p, seed,
                stop_token, prefix, deadline_s,
            )
        plen = 0
        pfx = None
        pfx_tokens = None
        if prefix is not None:
            with self._lock:
                if prefix not in self._prefixes:
                    raise ValueError(f"unknown prefix id {prefix}")
                pfx, plen, pfx_tokens = self._prefixes[prefix]
        if (
            self.windowed
            and (t > self.prompt_len or pfx is not None)
            and self.max_len % self.prompt_len
        ):
            # checked before any slot is claimed: ring chunked prefill
            # (long prompts, and any prefix continuation — it starts at
            # base=plen) needs bucket-aligned chunks (a mid-chunk ring
            # wrap would corrupt live entries). Bucket-sized prefixless
            # prompts never chunk, so unaligned windowed configs stay
            # valid for them.
            raise ValueError(
                f"windowed long prompts need max_len({self.max_len}) to "
                f"be a multiple of prompt_len({self.prompt_len}) so "
                "prefill chunks never wrap the ring mid-chunk"
            )
        if not self.windowed and plen + t > self.max_len:
            raise ValueError(
                f"prefix({plen}) + prompt({t}) > max_len {self.max_len}"
            )
        if not self.windowed and plen + t + max_new_tokens > self.max_len:
            raise ValueError(
                f"{plen}+{t}+{max_new_tokens} tokens would overflow "
                f"max_len={self.max_len} (windowed=True lifts this: the "
                "cache becomes a sliding ring)"
            )
        with self._lock:
            # claim only — the slot is owned (so no other submit takes it)
            # but inactive, so concurrent step() calls skip it while the
            # prefill below runs outside the lock
            try:
                slot = next(
                    i for i, r in enumerate(self._slots) if r is None
                )
            except StopIteration:
                return None
            rid = self._next_rid
            self._next_rid += 1
            req = _Request(
                rid, max_new_tokens, temperature=temperature, top_k=top_k,
                top_p=top_p, stop_token=stop_token,
                key=request_key(rid if seed is None else seed),
                # spec_step's proposal context — the prefix's tokens are
                # part of the stream the n-gram lookup should mine
                prompt=(
                    prompt if pfx_tokens is None
                    else np.concatenate([pfx_tokens, prompt])
                ),
            )
            self._slots[slot] = req
            self._slo.submit(rid, deadline_s)

        try:
            P = self.prompt_len
            if pfx is None and t <= P:
                # single-program fast path for bucket-sized prompts
                padded = np.zeros((1, P), np.int32)
                padded[0, :t] = prompt
                logits, (ks, vs), _ = self._prefill(jnp.asarray(padded))
                logits_row = logits[0, t - 1]
            elif self.windowed:
                # ring chunked prefill: exact sliding-window attention
                # for prompts of any length (the ring keeps the last W);
                # a registered prefix seeds the ring and the prompt
                # continues at absolute position plen (a bucket
                # multiple, so chunks stay wrap-free)
                logits, (ks, vs), last = self._stage_ring(
                    prompt, base=plen, ring=pfx
                )
                logits_row = logits[0, last]
            else:
                # chunked prefill (_stage_chunks): the staging cache
                # starts empty or preloaded with the registered prefix
                if pfx is None:
                    stage = self._empty_stage()
                else:
                    stage = self._load_prefix(self._empty_stage(), *pfx)
                logits, stage = self._stage_chunks(prompt, plen, stage, True)
                last = (t - 1) % P  # true last token's index in the chunk
                logits_row = logits[0, last]
                ks = stage[0][:, :, : self.max_len]
                vs = stage[1][:, :, : self.max_len]
            fill = plen + t
            # the first token stays a DEVICE scalar: materializing it
            # here would cost one device→host read per admission on the
            # submit path; _apply_pending fetches every queued
            # admission's first token in ONE gathered read instead
            first_dev = self._sample_first(logits_row, req, fill)
            if max_new_tokens == 1:
                # a one-token request finishes ON its prefill token:
                # fetch it now so the slot frees immediately (nothing
                # to decode — no hist row, no draft prefill either)
                first = int(first_dev)
                with self._lock:
                    req.fill0 = fill
                    req.tokens.append(first)
                    self._finish(slot)
                return rid
            # draft-prefill the full context (req.prompt already carries
            # prefix + prompt) OUTSIDE the state lock, like the target's
            # prefill — admission must never serialize device steps
            draft_kv = (
                self._draft.prefill_tokens(req.prompt)
                if self._draft is not None else None
            )
        except Exception:
            # release the claimed slot or n_slots failed prefills would
            # brick the server with every slot claimed-but-never-active
            with self._lock:
                self._slots[slot] = None
            raise

        # device n-gram context seed: the full known stream (context +
        # first pending token) as one padded row — staged into
        # self._hist at admission with a single static-shape write.
        # Windowed overruns stage the LAST H tokens in ring layout
        # (a % H, mirroring the KV ring) so post-wrap mining stays
        # exact; the non-windowed else is unreachable (submit validates
        # fill + budget ≤ max_len) and exists as a defensive fallback.
        H = self.max_len
        hist_row = np.full((H,), -1, np.int32)
        ctx = req.prompt
        if fill < H:
            hist_row[:fill] = ctx[:fill]
        elif self.windowed:
            # ring layout: token at absolute position a lives at a % H
            # (mirrors the KV ring), so post-wrap mining stays exact
            span = np.arange(fill - H, fill)
            hist_row[span % H] = ctx[span]
        else:
            hist_row[:] = ctx[:H]
        with self._lock:
            req.fill0 = fill
            # token 0 (and any finished-at-first-token bookkeeping, e.g.
            # a stop token landing on it) materializes at the next
            # _apply_pending, where every queued admission's
            # first token rides one gathered read — submit() itself never
            # blocks on the device
            self._pending.append(
                _PendingInsert(slot, ks, vs, first_dev, fill, req,
                               draft_kv=draft_kv, hist_row=hist_row)
            )
        return rid

    def _sample_first(self, logits_row, req: _Request, fill: int):
        """The request's first token from its prefill's last logits row,
        as a DEVICE scalar (``_apply_pending`` reads it)."""
        return self._sample1(
            logits_row,
            np.asarray([req.temperature], np.float32),
            np.asarray([req.top_k], np.int32),
            np.asarray([req.top_p], np.float32),
            np.asarray(req.key, np.uint32),
            np.int32(fill),
        )

    def _apply_pending(self) -> None:
        """Splice queued admissions into the device state.

        Caller holds _step_lock ONLY. Every queued admission's first
        token (a device scalar from submit's prefill sampler) is
        fetched in ONE gathered read (no launch, so nothing compiles
        whatever the number queued) — the admission-path analogue
        of the pumps' one-readback rule — and that fetch happens
        OUTSIDE self._lock: it may wait on an in-flight chunked
        prefill, and readers (submit/result/partials/stats) must not
        stall behind it."""
        with _trace.span("nns.pump.admit") as sp:
            admitted = 0
            with self._lock:
                batch = self._pending
                self._pending = []
            if batch:
                firsts = jax.device_get([p.first_tok for p in batch])
                with self._lock:
                    admitted = self._apply_batch_locked(batch, firsts)
            sp.set(admitted=admitted)

    def _apply_batch_locked(self, batch, firsts) -> int:
        """Host bookkeeping per queued admission, then ONE launch of the
        admit program (``_make_admit``) for every row that joins the
        batch: their per-slot writes ride one packed int32 buffer, row
        ``slot`` for slot ``slot``. Returns the number of rows applied."""
        self._pump_state_dirty = True  # admission changes pump state
        H = self.max_len
        rows = None
        admitted = 0
        for p, first in zip(batch, firsts):
            if self._slots[p.slot] is not p.req:
                continue  # request vanished (defensive; cannot happen)
            first = int(first)
            if p.blocks is not None:
                # paged: point the slot's block table at its blocks
                # BEFORE any finish path so _finish can free them
                row = np.zeros((self._blocks_per_slot,), np.int32)
                row[: len(p.blocks)] = p.blocks
                self._tables[p.slot] = row
                self._n_alloc[p.slot] = len(p.blocks)
                self._tables_dirty = True
            if not p.resumed:
                p.req.tokens.append(first)
                self._slo.admitted(p.req.rid)
                self._slo.first_token(p.req.rid)
                if p.req.finished():
                    # budget 1 or an immediate stop token: the request
                    # ends on its prefill token and never occupies the
                    # batch
                    self._finish(p.slot)
                    continue
            else:
                self._slo.admitted(p.req.rid)
            if p.fill < H:
                p.hist_row[p.fill] = first
            elif self.windowed:
                p.hist_row[p.fill % H] = first
            if p.blocks is None:
                self._cache = self._insert(self._cache, p.ks, p.vs, p.slot)
            if rows is None:
                rows = np.zeros((self.n_slots, H + _ADMIT_COLS), np.int32)
            r = rows[p.slot]
            r[:H] = p.hist_row
            r[H + _ADM_LIVE] = 1
            r[H + _ADM_TOK] = first
            r[H + _ADM_POS] = p.fill
            r[H + _ADM_TOPK] = p.req.top_k
            r[H + _ADM_TEMP] = np.float32(p.req.temperature).view(np.int32)
            r[H + _ADM_TOPP] = np.float32(p.req.top_p).view(np.int32)
            r[H + _ADM_KEY: H + _ADM_KEY + 2] = np.asarray(
                p.req.key, np.uint32
            ).view(np.int32)
            if p.draft_kv is not None and self._draft is not None:
                self._draft.admit(p.slot, p.draft_kv)
            self._active[p.slot] = True
            admitted += 1
        if rows is not None:
            try:
                (self._tok, self._pos, self._temp, self._topk, self._topp,
                 self._keys, self._hist) = self._admit(
                    self._tok, self._pos, self._temp, self._topk,
                    self._topp, self._keys, self._hist, rows,
                )
            except Exception as exc:  # the seven arrays were donated
                self._mark_failed(exc)
                raise
            self._n_admit_launches += 1
            self._n_admitted += admitted
        return admitted

    # -- paged KV: admission, chunked prefill, blocks, preemption ----------
    def _submit_paged(self, prompt, max_new_tokens, temperature, top_k,
                      top_p, seed, stop_token, prefix, deadline_s
                      ) -> Optional[int]:
        """Paged admission: claim a slot, match the prompt against the
        pool's prefix index (adopting shared blocks NOW so they cannot
        be reclaimed while queued), and enqueue a chunked-prefill job.
        No device work happens here, the request's key included
        (``request_key``): the lock is held for host bookkeeping only,
        and prefill advances in the pumps, interleaved with decode."""
        from nnstreamer_tpu.kv.sched import PrefillJob

        pfx_tokens = None
        if prefix is not None:
            with self._lock:
                if prefix not in self._prefixes_paged:
                    raise ValueError(f"unknown prefix id {prefix}")
                pfx_tokens = self._prefixes_paged[prefix][0]
        context = (
            prompt if pfx_tokens is None
            else np.concatenate([pfx_tokens, prompt]).astype(np.int32)
        )
        t = int(context.shape[0])
        if t + max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix+prompt({t})+{max_new_tokens} tokens would "
                f"overflow max_len={self.max_len}"
            )
        with self._lock:
            try:
                slot = next(
                    i for i, r in enumerate(self._slots) if r is None
                )
            except StopIteration:
                return None
            rid = self._next_rid
            self._next_rid += 1
            req = _Request(
                rid, max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, stop_token=stop_token,
                key=request_key(rid if seed is None else seed),
                prompt=context,
            )
            self._slots[slot] = req
            self._slo.submit(rid, deadline_s)
            # prefix matching happens lazily when the job starts staging
            # (_prefill_chunk_one): adopted blocks stay pinned only for
            # the short staging→activation window, so queued work never
            # starves the pool
            self._prefill_q.append(PrefillJob(slot, req, context))
        return rid

    def _match_and_adopt_locked(self, job, matchable) -> None:
        m = self._pool.match(matchable)
        for b in m.full:
            self._pool.adopt(b)
        if m.partial_block is not None:
            self._pool.adopt(m.partial_block)
        if m.n_tokens:
            self._pool.record_hit_tokens(m.n_tokens)
        job.matched_full = list(m.full)
        job.matched_partial = m.partial_block
        job.n_partial = m.n_partial
        job.base = m.n_tokens

    def _release_match_locked(self, job) -> None:
        """Drop a job's adopted prefix pins (sharing-degradation path)."""
        self._pool.free(job.matched_full)
        if job.matched_partial is not None:
            self._pool.free([job.matched_partial])
        job.matched_full = []
        job.matched_partial = None
        job.n_partial = 0
        job.base = 0

    def _advance_prefill(self) -> None:
        """Spend this pump's prefill budget on the queue, front job first,
        activating each job when it is staged + block-affordable.

        The budget is bucket programs (one ``prompt_len`` chunk each).
        ``prefill_chunks=0`` (the default) reads it off the queue at
        entry: ``max(1, jobs queued)``, so every request that waits for a
        lane is prefilled in the pump that finds it waiting and rides that
        pump's one admit launch — a lane left empty computes nothing for
        the whole decode launch, while the prefill program stalls whoever
        is live for the same milliseconds in whichever pump it runs. A
        long prompt alone in the queue is still chunked one bucket a pump,
        and what a decoding slot waits in a pump is bounded by the buckets
        of the jobs queued, not by one bucket. ``prefill_chunks=N >= 1``
        is the operator's cap on the largest stall: N buckets a pump,
        whatever is queued.

        The throttle exists ONLY to bound decode stalls — while nothing
        is decoding (no active slot, no activation pending), an idle
        decode plane keeps advancing until a job activates or the queue
        drains, whatever the budget.

        A pump that finds bucket-sized fresh prompts queued of which two
        or more fit one bucket, under a family that has a packed prompt
        program, lets them share programs (``_plan_packed``,
        ``_advance_prefill_packed``); every other pump runs the loop below.
        Caller holds _step_lock; _lock is taken only for bookkeeping."""
        queued = len(self._prefill_q)
        with _trace.span("nns.pump.prefill", prefill_q=queued) as sp:
            budget = self._prefill_chunks or max(1, queued)
            if queued >= 2 and self._prefill_packed is not None:
                plan = self._plan_packed(budget)
                if plan is not None and self._advance_prefill_packed(
                        plan, budget, sp):
                    return
            buckets = prompts = activated = 0
            tail = None  # what the last programs launched here produce
            while True:
                with self._lock:
                    job = self._prefill_q[0] if self._prefill_q else None
                    idle = not self._active.any() and not self._pending
                if job is None or (budget <= 0 and not idle):
                    break
                self._slo.prefilling(job.req.rid)
                if not job.done_staging():
                    # one bucket's outputs on the device at a time: a
                    # launch allocates its logits and stage when it is
                    # dispatched and the last one's are freed when its
                    # readers have run, so k launches queued at once would
                    # hold k of each (0.1-0.24 GB a bucket at the
                    # benchmark's widths) however deep the queue is
                    jax.block_until_ready(tail)
                    self._prefill_chunk_one(job)
                    tail = job.stage
                    budget -= 1
                    buckets += 1
                    prompts += job.done_staging()
                if job.done_staging():
                    if not self._prefill_finalize(job):
                        break  # blocks not affordable yet (watermark)
                    activated += 1
                    with self._lock:
                        if self._prefill_q and self._prefill_q[0] is job:
                            self._prefill_q.popleft()
                        # the landed arena and the sampled first token:
                        # the last readers of that bucket's outputs
                        tail = (self._cache, self._pending[-1].first_tok)
            self._note_prefill(sp, buckets, prompts, activated)

    def _note_prefill(self, sp, programs: int, prompts: int, activated: int,
                      packed: int = 0) -> None:
        """What one ``nns.pump.prefill`` span did, on the span and in
        ``stats()``: prompt programs launched (``packed`` of them held a bin
        of prompts), prompts whose prefill they completed, jobs activated."""
        if programs:
            self._n_prefill_pumps += 1
        self._n_prefill_programs += programs
        self._n_prefill_prompts += prompts
        self._n_prefill_packed_programs += packed
        sp.set(buckets=programs, programs=programs, prompts=prompts,
               activated=activated)

    def _packable_locked(self, job) -> bool:
        """``_prefill_chunk_one``'s condition for the single fast-path
        program, asked before the job starts staging: a fresh prompt no
        longer than the bucket that no registered prefix matches
        (``match`` takes no reference; a job that does match adopts in
        ``_prefill_chunk_one``, as ever)."""
        if (job.stage is not None or job.known_first is not None
                or not 0 < job.fill <= self.prompt_len):
            return False
        return bool(
            job.no_rematch
            or self._pool.match(job.tokens[:-1]).n_tokens == 0
        )

    def _plan_packed(self, budget: int):
        """What a pump that packs will run, in queue order: the bins of
        the packable jobs (first fit over the bins open so far, at most
        ``budget`` of them; a prompt of t tokens takes ceil(t / block_size)
        blocks' worth of rows, so every stage block belongs to one prompt)
        and, each in its queue place, the jobs that are not packable. A bin
        that ends with one prompt is that job again: a lone prompt takes the
        bucket program it always took. None where no bin holds two: the
        caller then does what it always did. A job no bin has room for
        stays queued."""
        P, bs = self.prompt_len, self.block_size
        with self._lock:
            jobs = [(j, self._packable_locked(j)) for j in self._prefill_q]
        plan: List[Any] = []
        bins: List[_PrefillBin] = []
        for job, packable in jobs:
            if not packable:
                plan.append(job)
                continue
            rows = -(-job.fill // bs) * bs
            into = next((b for b in bins if b.rows + rows <= P), None)
            if into is None:
                if len(bins) >= budget:
                    continue
                into = _PrefillBin()
                bins.append(into)
                plan.append(into)
            into.jobs.append(job)
            into.rows += rows
        plan = [
            item.jobs[0]
            if isinstance(item, _PrefillBin) and len(item.jobs) == 1
            else item for item in plan
        ]
        if not any(isinstance(item, _PrefillBin) for item in plan):
            return None
        return plan

    def _advance_prefill_packed(self, plan, budget: int, sp) -> bool:
        """A pump's prefill where prompts share programs: ``plan``'s items
        in order, a bin one program of the budget, a job that is not
        packed (longer than the bucket, a prefix hit, a resume, staged
        already, alone in its bin) through ``_prefill_chunk_one`` and
        ``_prefill_finalize``
        as in ``_advance_prefill``'s loop — but one the pool cannot afford
        holds nobody else up here. The same wait between programs: one
        program's outputs on the device at a time. Activations are queued
        in the order the jobs stood in the queue. False where nothing could
        be launched or activated (the pool affords none of them): nothing
        has changed then, and the caller's loop decides, as ever, whether
        the queue's head waits or can never be admitted."""
        programs = packed = prompts = activated = 0
        tail = None
        with self._lock:
            place = {id(j.req): i for i, j in enumerate(self._prefill_q)}
            n_pending = len(self._pending)

        def spend() -> bool:
            # the budget bounds decode stalls only: with nothing decoding
            # and nothing about to, go on until something activates
            if budget - programs > 0:
                return True
            with self._lock:
                return not self._active.any() and not self._pending

        for item in plan:
            if isinstance(item, _PrefillBin):
                if not spend():
                    continue
                jax.block_until_ready(tail)
                landed = self._prefill_bin(item)
                if landed:
                    programs += 1
                    packed += 1
                    prompts += landed
                    activated += landed
                    tail = (self._cache, self._pending[-1].first_tok)
                continue
            job = item
            while not job.done_staging() and spend():
                self._slo.prefilling(job.req.rid)
                jax.block_until_ready(tail)
                self._prefill_chunk_one(job)
                tail = job.stage
                programs += 1
                prompts += job.done_staging()
            if job.done_staging() and self._prefill_finalize(job):
                activated += 1
                with self._lock:
                    self._unqueue_locked([job])
                    tail = (self._cache, self._pending[-1].first_tok)
        with self._lock:
            self._pending[n_pending:] = sorted(
                self._pending[n_pending:],
                key=lambda p: place.get(id(p.req), len(place)),
            )
        if not programs and not activated:
            return False
        self._note_prefill(sp, programs, prompts, activated, packed)
        return True

    def _unqueue_locked(self, jobs) -> None:
        """Take ``jobs`` out of the prefill queue, wherever they stand."""
        gone = {id(j) for j in jobs}
        keep = [j for j in self._prefill_q if id(j) not in gone]
        self._prefill_q.clear()
        self._prefill_q.extend(keep)

    def _prefill_bin(self, b: _PrefillBin) -> int:
        """One packed program for the bin's prompts, ONE landing of every
        prompt's rows into its own blocks (``land_stage`` at the bucket's
        shape: stage block i to arena block ``ids[i]``), one launch of the
        step's sampler for the first tokens of every ``_FIRST_ROWS`` of
        them; then each job's activation is queued, in bin order. Returns
        the number of prompts landed.

        Blocks are allocated before anything is launched, under the
        watermark ``_prefill_finalize`` keeps (one decode-growth block of
        headroom for every live request, the bin's own included), so what
        is launched lands whole; a job the pool cannot afford now is left
        out and stays queued."""
        from nnstreamer_tpu.kv.blocks import NoBlocksError

        P, bs = self.prompt_len, self.block_size
        K = P // bs
        got: List[Tuple[Any, List[int]]] = []
        with self._lock:
            n_live = int(self._active.sum()) + len(self._pending)
            for job in b.jobs:
                need = -(-job.fill // bs)
                if self._pool.available() < need + n_live + len(got):
                    continue
                try:
                    got.append((job, self._pool.alloc(need)))
                except NoBlocksError:
                    continue
        if not got:
            return 0
        tokens = np.full((1, P), self._family.pad_id, np.int32)
        positions = np.zeros((P,), np.int32)
        segment = np.full((P,), -1, np.int32)
        last = np.full((K,), -1, np.int32)
        ids = np.zeros((K,), np.int32)
        valid = np.zeros((K,), bool)
        row = 0
        for r, (job, blocks) in enumerate(got):
            self._slo.prefilling(job.req.rid)
            t = job.fill
            tokens[0, row: row + t] = job.tokens
            positions[row: row + t] = np.arange(t)
            segment[row: row + t] = r
            last[r] = row + t - 1
            i = row // bs
            ids[i: i + len(blocks)] = blocks
            valid[i: i + len(blocks)] = True
            row += len(blocks) * bs
        self._n_prefill_chunk_programs += 1
        logits, stage = self._prefill_packed(tokens, positions, segment, last)
        # the dense family has no slot leaves: the lane argument is unused
        self._cache = self._land_stage(
            self._cache, stage, ids, valid, np.int32(0)
        )
        firsts: List[Any] = []
        for r0 in range(0, len(got), _FIRST_ROWS):
            reqs = [job.req for job, _ in got[r0: r0 + _FIRST_ROWS]]
            n = len(reqs)
            rows = np.zeros((_FIRST_ROWS,), np.int32)
            temp = np.zeros((_FIRST_ROWS,), np.float32)
            topk = np.zeros((_FIRST_ROWS,), np.int32)
            topp = np.ones((_FIRST_ROWS,), np.float32)
            keys = np.zeros((_FIRST_ROWS, 2), np.uint32)
            fill = np.zeros((_FIRST_ROWS,), np.int32)
            rows[:n] = np.arange(r0, r0 + n)
            temp[:n] = [q.temperature for q in reqs]
            topk[:n] = [q.top_k for q in reqs]
            topp[:n] = [q.top_p for q in reqs]
            keys[:n] = [q.key for q in reqs]
            fill[:n] = [job.fill for job, _ in got[r0: r0 + n]]
            firsts += self._sample_rows(
                logits, rows, temp, topk, topp, keys, fill
            )[:n]
        with self._lock:
            for (job, blocks), first in zip(got, firsts):
                self._pool.register(job.tokens, blocks)
                hist_row = np.full((self.max_len,), -1, np.int32)
                hist_row[: job.fill] = job.tokens
                job.req.fill0 = job.fill
                self._pending.append(
                    _PendingInsert(
                        job.slot, None, None, first, job.fill, job.req,
                        hist_row=hist_row, blocks=blocks,
                    )
                )
            self._unqueue_locked([job for job, _ in got])
        return len(got)

    def _prefill_chunk_one(self, job) -> None:
        """One ``prompt_len`` bucket of chunked prefill for ``job``
        (device work — caller holds _step_lock only)."""
        self._n_prefill_chunk_programs += 1
        P = self.prompt_len
        ctx = job.tokens
        t = job.fill
        if job.stage is None:
            if not job.no_rematch:
                with self._lock:
                    # match context[:-1] for fresh requests: the LAST
                    # token must run through the model even on a full
                    # prefix hit — its logits pick the first generated
                    # token. Resumes (known_first set) may match their
                    # whole context. The sharing-degradation fallback
                    # sets no_rematch: re-adopting the released prefix
                    # here would restore the exact pre-degrade state and
                    # livelock the queue head.
                    self._match_and_adopt_locked(
                        job,
                        ctx if job.known_first is not None else ctx[:-1],
                    )
            if (job.base == 0 and job.matched_partial is None
                    and t <= P and job.known_first is None):
                # bucket-sized fresh prompt: the SAME single fast-path
                # program the slot layout admits through (bitwise parity
                # with contiguous admission)
                padded = np.full((1, P), self._family.pad_id, np.int32)
                padded[0, :t] = ctx
                logits, job.stage, _ = self._prefill(jnp.asarray(padded))
                job.logits_row = logits[0, t - 1]
                job.cpos = t
                return
            stage = self._empty_stage()
            # seed matched prefix K/V into the stage so continuation
            # chunks attend it (fp: bitwise the originally staged
            # values) — all matched blocks in ONE seed_stage launch
            bs = self.block_size
            seeds = list(job.matched_full)
            if job.matched_partial is not None:
                seeds.append(job.matched_partial)
            if seeds:
                ids = np.zeros((self._stage_len // bs,), np.int32)
                ids[: len(seeds)] = seeds
                stage = self._seed_stage(
                    self._cache, stage, jnp.asarray(ids),
                    jnp.asarray(len(seeds), jnp.int32),
                )
            job.stage = stage
        if job.done_staging():
            return
        start = job.base + job.cpos
        final = start + P >= t
        logits, stage, n = self._chunk_step(
            ctx[start:], start, job.stage,
            final and job.known_first is None,
        )
        if logits is not None:
            job.logits_row = logits[0, n - 1]
        job.stage = stage
        job.cpos += n

    def _prefill_finalize(self, job) -> bool:
        """Allocate the job's blocks, land staged K/V (and, where the
        family has slot leaves, the state the prompt reached into the
        slot's row), register its prefix, and queue the activation.
        False = not affordable yet
        under the watermark (every live request keeps one decode-growth
        block of headroom), so the job waits — admission can defer but
        never OOM the decode plane."""
        from nnstreamer_tpu.kv.blocks import NoBlocksError

        bs = self.block_size
        t = job.fill
        n_blocks = -(-t // bs)
        n_full = len(job.matched_full)
        fresh_needed = n_blocks - n_full  # includes the CoW copy
        with self._lock:
            # decoding now, or finalized earlier in this pump and live at
            # its launch: each keeps a decode-growth block of headroom
            n_live = int(self._active.sum()) + len(self._pending)
            if fresh_needed > 0 and (
                self._pool.available() < fresh_needed + n_live
            ):
                if n_live == 0:
                    # nothing is decoding, so waiting cannot help
                    if job.matched_full or job.matched_partial is not None:
                        # give back the adopted prefix pins and restart
                        # staging unshared — degrade sharing to progress
                        self._release_match_locked(job)
                        job.stage = None
                        job.cpos = 0
                        job.no_rematch = True
                        return False
                    raise RuntimeError(
                        "kv pool cannot admit a request with nothing "
                        "decoding: kv_blocks too small for the prompt, "
                        "or registered prefixes pin too much of the pool"
                    )
                return False
            try:
                fresh = (
                    self._pool.alloc(fresh_needed)
                    if fresh_needed > 0 else []
                )
            except NoBlocksError:
                return False
            if job.matched_partial is not None and fresh:
                self._pool.note_cow()  # first fresh block is the copy
        blocks = list(job.matched_full) + fresh
        # land staged K/V into the fresh blocks (adopted full blocks
        # already hold theirs; the CoW block's copied prefix rides the
        # seeded stage, so one write covers copy + continuation) — the
        # whole span in ONE land_stage launch
        if job.stage is not None:
            if n_blocks > n_full:
                # one id slot per stage block — the bucket-wide fast
                # path stage and the full chunked stage each size it
                stage_blocks = job.stage[0].shape[2] // bs
                ids = np.zeros((stage_blocks,), np.int32)
                valid = np.zeros((stage_blocks,), bool)
                for i in range(n_full, n_blocks):
                    ids[i] = blocks[i]
                    valid[i] = True
                self._cache = self._land_stage(
                    self._cache, job.stage, jnp.asarray(ids),
                    jnp.asarray(valid), np.int32(job.slot),
                )
        elif job.matched_partial is not None and fresh:
            # fully-matched resume ending in a partial block: pure
            # device-side copy-on-write
            self._cache = self._copy_block(
                self._cache, jnp.asarray(job.matched_partial, jnp.int32),
                jnp.asarray(blocks[n_full], jnp.int32),
            )
        job.stage = None  # release staging memory
        with self._lock:
            if job.matched_partial is not None:
                # the CoW copy replaced the shared partial block
                self._pool.free([job.matched_partial])
            self._pool.register(job.tokens, blocks)
        req = job.req
        if job.known_first is not None:
            first_dev: Any = int(job.known_first)
        else:
            first_dev = self._sample_first(job.logits_row, req, t)
        job.logits_row = None
        hist_row = np.full((self.max_len,), -1, np.int32)
        hist_row[:t] = job.tokens[: self.max_len]
        with self._lock:
            if not job.resumed:
                req.fill0 = t
            self._pending.append(
                _PendingInsert(
                    job.slot, None, None, first_dev, t, req,
                    hist_row=hist_row, blocks=blocks,
                    resumed=job.resumed,
                )
            )
        return True

    def _ensure_decode_room_locked(self, n: int) -> None:
        """Watermark decode-growth accounting: every active slot gets
        blocks covering its next ``n`` token writes, preempting the
        youngest other request on exhaustion (its blocks free, shared
        prefix blocks stay cached, and it re-enters the prefill queue
        to resume from whatever prefix still matches) — eviction and
        re-prefill instead of OOM. Caller holds _lock."""
        from nnstreamer_tpu.kv.blocks import NoBlocksError
        from nnstreamer_tpu.kv.sched import choose_victim

        bs = self.block_size
        for s, req in enumerate(self._slots):
            if req is None or not self._active[s]:
                continue
            pos = req.fill0 + len(req.tokens) - 1
            last = min(pos + int(n) - 1, self.max_len - 1)
            need = last // bs + 1
            while self._n_alloc[s] < need:
                try:
                    (b,) = self._pool.alloc(1)
                except NoBlocksError:
                    victim = choose_victim(self._slots, self._active, s)
                    if victim is None:
                        raise RuntimeError(
                            "kv pool exhausted with one active request "
                            "left: kv_blocks cannot cover a single "
                            "stream's growth — raise kv_blocks"
                        ) from None
                    self._preempt_locked(victim)
                    continue
                self._tables[s, self._n_alloc[s]] = b
                self._n_alloc[s] += 1
                self._tables_dirty = True

    def _preempt_locked(self, slot: int) -> None:
        """Evict ``slot``'s request: free its blocks (a family's per-slot
        state is dropped with them: the row is overwritten when the job
        lands again) and queue a re-prefill job for its full known stream
        (prompt + generated
        tokens, pending token carried as known_first so the resumed
        stream is exactly the original — greedy AND sampled, since
        sampling keys by (seed, position))."""
        from nnstreamer_tpu.kv.sched import PrefillJob

        req = self._slots[slot]
        self._pool.free(self._tables[slot, : self._n_alloc[slot]].tolist())
        self._tables[slot] = 0
        self._n_alloc[slot] = 0
        self._tables_dirty = True
        self._active[slot] = False
        self._pump_state_dirty = True
        self._slo.preempted(req.rid)
        if len(req.tokens) > 1:
            context = np.concatenate([
                req.prompt, np.asarray(req.tokens[:-1], np.int32)
            ])
        else:
            context = np.asarray(req.prompt, np.int32)
        self._prefill_q.append(PrefillJob(
            slot, req, context, known_first=int(req.tokens[-1]),
            resumed=True,
        ))

    # -- live migration (kv/migrate.py; docs/llm-serving.md) ---------------
    def _span_leaf_template(self):
        """(dtype, per-block shape) per arena leaf, jax leaf order —
        the geometry a span must match to be adoptable here."""
        return [
            (str(np.dtype(leaf.dtype).name),
             (leaf.shape[0],) + tuple(leaf.shape[2:]))
            for leaf in jax.tree_util.tree_leaves(self._cache)
        ]

    def probe_prefix(self, tokens) -> int:
        """Leading tokens of ``tokens`` whose K/V this pool already
        holds in FULL indexed blocks — the migration warm probe.
        Read-only (nothing is adopted); the answer feeds
        ``RequestSpan.strip_shared`` on the sending side so a warm
        migration ships only the unshared suffix."""
        if not self._paged:
            return 0
        toks = np.asarray(tokens, np.int32).reshape(-1)
        with self._lock:
            m = self._pool.match(toks)
        return len(m.full) * self.block_size

    def extract_request(self, rid: int, remove: bool = True):
        """Serialize request ``rid``'s live state into a
        :class:`~nnstreamer_tpu.kv.migrate.RequestSpan`: the request
        row, the rolling-CRC prefix hashes, and every KV block's RAW
        arena bytes (int8 payloads ship quantized + scales verbatim —
        the round trip through ``read_block`` would dequantize and
        break the bitwise guarantee). ``remove=True`` (migration) frees
        the slot and blocks — registered blocks park in the pool's
        cached tier, adoptable by later prompts; ``remove=False`` is
        the non-destructive checkpoint read. Under ``_step_lock`` like
        ``register_prefix``: the arena reads must serialize with
        donated step/pump launches."""
        from nnstreamer_tpu.kv.blocks import roll_hash
        from nnstreamer_tpu.kv.migrate import (
            BlockRecord,
            RequestSpan,
            SpanStateError,
            block_crc,
        )

        if not self._paged:
            raise SpanStateError(
                "request migration needs kv_layout='paged'"
            )
        self._check_failed()
        self._refuse("migration")
        with self._step_lock:
            self._apply_pending()
            with self._lock:
                slot = None
                for s, r in enumerate(self._slots):
                    if r is not None and r.rid == rid:
                        slot = s
                        break
                if slot is None or not self._active[slot]:
                    raise SpanStateError(
                        f"request {rid} is not extractable: only an "
                        "actively decoding request has a KV span "
                        "(settle the prefill queue first — queued/"
                        "prefilling requests re-submit, they do not "
                        "migrate)"
                    )
                req = self._slots[slot]
                bs = self.block_size
                n_kv = req.fill0 + len(req.tokens) - 1
                n_blocks = -(-n_kv // bs)
                blocks = self._tables[slot, :n_blocks].tolist()
                stream = np.concatenate([
                    np.asarray(req.prompt, np.int32),
                    np.asarray(req.tokens, np.int32),
                ])[:n_kv]
                # one packed gather per arena leaf — the exact resident
                # bytes, fetched through the same lock discipline as
                # snapshot()
                ids = jnp.asarray(np.asarray(blocks, np.int32))
                raw = [
                    np.asarray(leaf[:, ids])
                    for leaf in jax.tree_util.tree_leaves(self._cache)
                ]
                records = []
                hashes = []
                h = 0
                for i in range(n_blocks):
                    n_tok = min(bs, n_kv - i * bs)
                    payload = [
                        np.ascontiguousarray(r[:, i]).tobytes()
                        for r in raw
                    ]
                    records.append(
                        BlockRecord(n_tok, block_crc(payload), payload)
                    )
                    if n_tok == bs:
                        h = roll_hash(h, stream[i * bs: (i + 1) * bs])
                        hashes.append(h)
                rec = self._slo.record(rid)
                deadline = None
                if rec is not None and rec.deadline_s is not None:
                    deadline = rec.deadline_s - (
                        _time.perf_counter() - rec.t_submit
                    )
                span = RequestSpan(
                    block_size=bs,
                    leaves=self._span_leaf_template(),
                    cache_dtype=(
                        "int8" if self._quantized
                        else str(np.dtype(self.compute_dtype).name)
                    ),
                    rid=rid,
                    prompt=np.asarray(req.prompt, np.int32).copy(),
                    tokens=list(req.tokens),
                    fill0=int(req.fill0),
                    budget=int(req.budget),
                    temperature=float(req.temperature),
                    top_k=int(req.top_k),
                    top_p=float(req.top_p),
                    stop_token=req.stop_token,
                    key=np.asarray(req.key, np.uint32).copy(),
                    deadline_s=deadline,
                    preemptions=(
                        rec.preemptions if rec is not None else 0
                    ),
                    prefix_hashes=hashes,
                    blocks=records,
                )
                if remove:
                    self._pool.free(blocks)
                    self._tables[slot] = 0
                    self._n_alloc[slot] = 0
                    self._tables_dirty = True
                    self._active[slot] = False
                    self._pump_state_dirty = True
                    self._slots[slot] = None
                    self._slo.migrated(rid)
                    self._n_migrations_out += 1
                    if self._obs_reg is not None:
                        self._obs_reg.counter(
                            "nns_kv_migrations_total", direction="out"
                        ).inc()
        return span

    def adopt_request(self, span) -> int:
        """Land a peer's :class:`RequestSpan` into THIS batcher and
        continue decoding it: full blocks the prefix index already
        holds are shared by refcount (the warm path — stripped payloads
        must be covered here or :class:`SpanPayloadMissingError`), the
        rest land their raw payloads into freshly allocated blocks, and
        the request re-enters the batch through the resumed-admission
        path (``known_first`` = the pending token, so no re-sampling:
        the continued stream is bitwise the source's). Returns the NEW
        local rid. Raises :class:`SpanCapacityError` (no slot / no
        blocks / budget would overflow ``max_len``) without mutating
        anything."""
        from nnstreamer_tpu.kv.blocks import NoBlocksError
        from nnstreamer_tpu.kv.migrate import (
            SpanCapacityError,
            SpanFormatError,
            SpanPayloadMissingError,
        )

        if not self._paged:
            raise SpanFormatError(
                "request migration needs kv_layout='paged'"
            )
        self._check_failed()
        self._refuse("migration")
        bs = self.block_size
        if span.block_size != bs:
            raise SpanFormatError(
                f"KV span block_size {span.block_size} != this "
                f"batcher's {bs}"
            )
        if list(span.leaves) != self._span_leaf_template():
            raise SpanFormatError(
                "KV span arena geometry mismatch (layers/heads/dims or "
                "cache dtype differ — migrate between identically "
                "configured batchers)"
            )
        if span.fill0 + span.budget > self.max_len:
            raise SpanCapacityError(
                f"span needs fill0+budget={span.fill0 + span.budget} "
                f"positions but max_len={self.max_len}"
            )
        n_kv = span.n_kv
        n_blocks = -(-n_kv // bs)
        stream = span.kv_tokens
        with self._step_lock:
            self._apply_pending()
            with self._lock:
                try:
                    slot = next(
                        i for i, r in enumerate(self._slots) if r is None
                    )
                except StopIteration:
                    raise SpanCapacityError(
                        f"no free slot ({self.n_slots} occupied)"
                    ) from None
                m = self._pool.match(stream)
                n_shared = min(len(m.full), n_blocks)
                shared = list(m.full[:n_shared])
                for i, rec in enumerate(span.blocks):
                    if rec.payload is None and i >= n_shared:
                        raise SpanPayloadMissingError(
                            f"block {i} was stripped by the sender but "
                            "this pool's prefix index does not cover it"
                        )
                for b in shared:
                    self._pool.adopt(b)
                if n_shared:
                    self._pool.record_hit_tokens(n_shared * bs)
                try:
                    fresh = (
                        self._pool.alloc(n_blocks - n_shared)
                        if n_blocks > n_shared else []
                    )
                except NoBlocksError:
                    self._pool.free(shared)
                    raise SpanCapacityError(
                        f"pool cannot host the span: needs "
                        f"{n_blocks - n_shared} fresh blocks, "
                        f"{self._pool.available()} available"
                    ) from None
                rid = self._next_rid
                self._next_rid += 1
                req = _Request(
                    rid, span.budget, temperature=span.temperature,
                    top_k=span.top_k, top_p=span.top_p,
                    stop_token=span.stop_token,
                    key=np.asarray(span.key, np.uint32),
                    prompt=np.asarray(span.prompt, np.int32),
                )
                req.tokens = list(span.tokens)
                req.fill0 = int(span.fill0)
                self._slots[slot] = req
            blocks = shared + fresh
            if fresh:
                # decode every shipped payload on host BEFORE the first
                # donated device write, so a malformed span can never
                # half-mutate the arena
                per_leaf = []
                for j, (dt, shape) in enumerate(span.leaves):
                    per_leaf.append(np.stack([
                        np.frombuffer(
                            span.blocks[i].payload[j], dtype=np.dtype(dt)
                        ).reshape(shape)
                        for i in range(n_shared, n_blocks)
                    ], axis=1))
                try:
                    treedef = jax.tree_util.tree_structure(self._cache)
                    leaves = jax.tree_util.tree_leaves(self._cache)
                    ids = jnp.asarray(np.asarray(fresh, np.int32))
                    self._cache = jax.tree_util.tree_unflatten(treedef, [
                        self._adopt_scatter(leaf, ids, jnp.asarray(vals))
                        for leaf, vals in zip(leaves, per_leaf)
                    ])
                except Exception as exc:  # donated mid-write: latch
                    self._mark_failed(exc)
                    raise
            with self._lock:
                self._pool.register(stream, blocks)
                rec = self._slo.submit(rid, span.deadline_s)
                rec.preemptions = int(span.preemptions)
                hist_row = np.full((self.max_len,), -1, np.int32)
                hist_row[:n_kv] = stream[: self.max_len]
                self._pending.append(_PendingInsert(
                    slot, None, None, int(span.tokens[-1]), n_kv, req,
                    hist_row=hist_row, blocks=blocks, resumed=True,
                ))
                self._n_migrations_in += 1
            self._apply_pending()
        if self._obs_reg is not None:
            self._obs_reg.counter(
                "nns_kv_migrations_total", direction="in"
            ).inc()
        return rid

    def resume_from_span(self, span) -> int:
        """Deadline-aware re-prefill fallback (the PR-10 eviction-resume
        path): when no peer accepts the span, re-admit the request from
        its token stream — the prefix index supplies whatever KV
        survived in the cached tier, chunked prefill recomputes the
        rest, and ``known_first`` pins the pending token so the
        continued stream is exactly the original. Returns the new rid;
        the span's remaining deadline and preemption count carry over."""
        from nnstreamer_tpu.kv.migrate import (
            SpanCapacityError,
            SpanFormatError,
        )
        from nnstreamer_tpu.kv.sched import PrefillJob

        if not self._paged:
            raise SpanFormatError(
                "request migration needs kv_layout='paged'"
            )
        self._check_failed()
        self._refuse("migration")
        if span.fill0 + span.budget > self.max_len:
            raise SpanCapacityError(
                f"span needs fill0+budget={span.fill0 + span.budget} "
                f"positions but max_len={self.max_len}"
            )
        with self._lock:
            try:
                slot = next(
                    i for i, r in enumerate(self._slots) if r is None
                )
            except StopIteration:
                raise SpanCapacityError(
                    f"no free slot ({self.n_slots} occupied)"
                ) from None
            rid = self._next_rid
            self._next_rid += 1
            req = _Request(
                rid, span.budget, temperature=span.temperature,
                top_k=span.top_k, top_p=span.top_p,
                stop_token=span.stop_token,
                key=np.asarray(span.key, np.uint32),
                prompt=np.asarray(span.prompt, np.int32),
            )
            req.tokens = list(span.tokens)
            req.fill0 = int(span.fill0)
            self._slots[slot] = req
            rec = self._slo.submit(rid, span.deadline_s)
            rec.preemptions = int(span.preemptions)
            if len(span.tokens) > 1:
                context = np.concatenate([
                    np.asarray(span.prompt, np.int32),
                    np.asarray(span.tokens[:-1], np.int32),
                ])
            else:
                context = np.asarray(span.prompt, np.int32)
            self._prefill_q.append(PrefillJob(
                slot, req, context,
                known_first=int(span.tokens[-1]), resumed=True,
            ))
            self._n_resumes += 1
        if self._obs_reg is not None:
            self._obs_reg.counter(
                "nns_request_resumes_total", kind="reprefill"
            ).inc()
        return rid

    # -- failure containment (donated-state launches) ----------------------
    def _mark_failed(self, exc: Exception) -> None:
        """A step/pump program raised after dispatch: the donated cache
        buffers are gone while the attributes still point at them. Latch
        the failure so every later call raises a clear typed error
        instead of a cryptic deleted-buffer one. Lock-free write
        (GIL-atomic; callers may already hold _lock/_step_lock)."""
        if self._failed is None:
            self._failed = exc

    def _refuse(self, feature: str) -> None:
        """A feature the batcher's family does not carry refuses by name."""
        refuse_unsupported(self._family, {feature: True})

    def _check_failed(self) -> None:
        if self._failed is not None:
            raise BatcherFailedError(
                f"batcher is failed: a prior step/pump launch raised "
                f"{type(self._failed).__name__}: {self._failed}; the "
                "donated device state is invalid — build a new batcher"
            ) from self._failed

    def step(self) -> Dict[int, int]:
        """Advance every active slot one token; returns {rid: token}. A
        pump of one: there is one decode program per family and layout."""
        return {rid: t[0] for rid, t in self.step_pump(1).items()}

    def _pump_span(self, n_steps: int):
        """The ``nns.pump`` span of one entry into the decode loop, with
        what the host knows at entry (no lock: the counts are for a
        reader of the trace, not for control)."""
        return _trace.span(
            "nns.pump", n_steps=n_steps, active=int(self._active.sum()),
            prefill_q=len(self._prefill_q),
        )

    def _harvest_rows_locked(
        self, active_np, rows
    ) -> Tuple[Dict[int, List[int]], int]:
        """Append per-slot emitted rows (−1-padded, [B, ...] iterable of
        row iterables) into their requests until budget/stop finishes
        them; returns ({rid: tokens}, n_emitted). One implementation of
        the budget/stop truncation discipline for every pump commit
        path (caller holds _lock). Where the previous harvest left a slot
        decoding, the time since it is the gap that slot waited
        (``gap_ms``, ``nns_decode_gap_ms``)."""
        now = _time.perf_counter()
        gap = {}
        if self._harvest_t is not None:
            gap["gap_ms"] = (now - self._harvest_t) * 1e3
            if self._gap_hist is not None:
                self._gap_hist.observe(gap["gap_ms"])
        with _trace.span("nns.pump.harvest", **gap):
            out: Dict[int, List[int]] = {}
            n_em = 0
            for s, req in enumerate(self._slots):
                if req is None or not active_np[s]:
                    continue
                got: List[int] = []
                for row in rows(s):
                    for t in row:
                        if t < 0:
                            break
                        req.tokens.append(int(t))
                        got.append(int(t))
                        n_em += 1
                        if req.finished():
                            break
                    if req.finished():
                        break
                if got:
                    out[req.rid] = got
                if req.finished():
                    self._finish(s)
            self._harvest_t = now if self._active.any() else None
            return out, n_em

    def _pump_host_state(self, active_np):
        """Per-slot budget remaining + stop ids for a device pump
        (host-known state; [B] int32 each). Only the dirty-rebuild path
        of :meth:`_pump_state_locked` calls this now."""
        remaining = np.zeros((self.n_slots,), np.int32)
        stop = np.full((self.n_slots,), -1, np.int32)
        for s, req in enumerate(self._slots):
            if req is None or not active_np[s]:
                continue
            remaining[s] = req.budget - len(req.tokens)
            if req.stop_token is not None:
                stop[s] = req.stop_token
        return remaining, stop

    def _pump_state_locked(self):
        """Device-carried pump state: (budget remaining, stop ids,
        active mask) as [B] device arrays.

        The pump scans already compute next-pump values for all three
        (budget decremented, stops latched, lanes idled out) — so the
        arrays are CARRIED on device across pumps and the host rebuild +
        H2D ship happens only when the dirty flag says a slot actually
        changed outside a pump (submit admission, a finished/preempted
        request, or a host-stepped path). A steady pump-only drain ships
        ZERO host state — pinned by the no-new-H2D regression test in
        tests/test_pumps.py. Caller holds _lock."""
        if self._pump_state_dirty:
            remaining, stop = self._pump_host_state(self._active)
            self._budget_dev = self._pin(jnp.asarray(remaining))
            self._stop_dev = self._pin(jnp.asarray(stop))
            self._active_dev = self._pin(jnp.asarray(self._active.copy()))
            self._pump_state_dirty = False
            self._host_state_builds += 1
        return self._budget_dev, self._stop_dev, self._active_dev

    def _tables_device_locked(self):
        """Cached device copy of the block tables (paged), re-shipped
        only when an allocation/preemption/admission changed a row."""
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self._tables)
            self._tables_dirty = False
        return self._tables_dev

    def _live_blocks_locked(self) -> int:
        """Arena blocks the next decode step's attention must read: the
        sum over active slots of ceil(fill / block_size), from the host's
        own count of each slot's history — what a launch's attention
        time should go with, whatever the tables' reach (the
        ``live_blocks`` attribute of ``nns.pump.launch``). Caller holds
        _lock."""
        bs = self.block_size
        return sum(
            -(-(req.fill0 + len(req.tokens) - 1) // bs)
            for s, req in enumerate(self._slots)
            if req is not None and self._active[s]
        )

    def step_pump(self, n: int = 8) -> Dict[int, List[int]]:
        """Advance every active slot by up to ``n`` tokens in ONE
        compiled program (lax.scan over the batched step) with ONE
        [B, n] device→host read at the end — the serving hot loop
        shaped for the chip, not the host: per-token pumping pays a
        full host↔device round trip per token, while a pump
        amortizes it n ways. Slots hit their budget or stop token ON
        DEVICE and idle out (-1 lanes); admissions join at the next
        pump, so admission latency is bounded by one pump — pump small
        when latency-sensitive, large for throughput. Returns
        {rid: [tokens emitted this pump]}. Role-match: the reference's
        single-invoke-per-buffer filter loop
        (gst/nnstreamer/tensor_filter/tensor_filter.c) batched along
        the token axis instead."""
        self._check_failed()
        with self._pump_span(int(n)), self._step_lock:
            return self._pump_locked(int(n))

    def _pump_locked(self, n: int) -> Dict[int, List[int]]:
        """step_pump's body; caller holds _step_lock. The compiled
        program runs OUTSIDE the state lock (admission only needs the lock
        for its bookkeeping, so submit() never waits on an in-flight
        device step); slots admitted while a pump is in flight join at the
        next one."""
        self._layout.advance_prefill(self)
        self._apply_pending()
        with _trace.span("nns.pump.prepare"), self._lock:
            if not self._active.any():
                return {}
            # before the active snapshot: making room may preempt
            # (deactivate) a victim slot
            self._layout.ensure_room(self, n)
            active_np = self._active.copy()
            sampling = self._any_sampling_locked(active_np)
            budget_dev, stop_dev, active_dev = self._pump_state_locked()
            live_blocks = self._layout.live_blocks(self)
            state_bytes = self._layout.state_bytes(self)
            carried, fixed = self._layout.args(self)
            args = (
                self._tok, self._pos, active_dev, carried, self._hist,
                budget_dev, stop_dev, self._temp, self._topk, self._topp,
                self._keys, *fixed,
            )
        fn = self._pump_sampling if sampling else self._pump_greedy
        try:
            with _trace.span("nns.pump.launch",
                             active=int(active_np.sum()),
                             live_blocks=live_blocks,
                             state_bytes=state_bytes):
                emits, tok, pos, act, carried, hist, budget = fn(
                    *args, n_steps=n
                )
            with _trace.span("nns.pump.wait"):
                emits_np = np.asarray(emits)  # ONE [B, n] transfer
            aux_np = None
            if self._family.aux_names:
                aux_np = emits_np[self.n_slots * n:]
                emits_np = emits_np[: self.n_slots * n].reshape(
                    self.n_slots, n
                )
        except Exception as exc:
            # the launch donated the carried cache and _hist: a raise
            # here leaves them consumed — latch the failure so later
            # calls get BatcherFailedError, not a cryptic deleted-buffer
            # error (submit()'s rollback analogue)
            self._mark_failed(exc)
            raise
        with self._lock:
            self._layout.commit(self, carried)
            self._hist = self._pin(hist)
            self._tok = self._pin(tok)
            self._pos = self._pin(pos)
            # the scan's carried pump state becomes next pump's input
            self._budget_dev = self._pin(budget)
            self._active_dev = self._pin(act)
            out, n_em = self._harvest_rows_locked(
                active_np, lambda s: (emits_np[s],)
            )
            self._n_steps += n
            self._n_tokens += n_em
            if aux_np is not None:
                self._note_aux_locked(aux_np)
            return out

    def _any_sampling_locked(self, active_np) -> bool:
        """Whether any live slot samples (else the greedy program runs)."""
        return any(
            req is not None and active_np[s] and req.temperature > 0
            for s, req in enumerate(self._slots)
        )

    def _note_aux_locked(self, aux_np) -> None:
        """One harvested pump's family counters (``aux_names``, summed on
        the device over the pump's steps and layers): totals for stats(),
        and the family's own metrics and trace instant."""
        got = {k: int(v) for k, v in zip(self._family.aux_names, aux_np)}
        for k, v in got.items():
            self._aux_totals[k] = self._aux_totals.get(k, 0) + v
        self._family.note_aux(got, self._obs_reg)

    def spec_pump(
        self, rounds: int = 8, k: int = 4, ngram: int = 2
    ) -> Dict[int, List[int]]:
        """``rounds`` whole speculative rounds per program launch —
        propose → verify → accept → commit scanned ON DEVICE, proposals
        from device_ngram_propose (or an in-scan draft model), one
        packed int32 read back per pump (emitted tokens + acceptance
        telemetry). The host spec_step pays two device reads plus
        Python mining per round; this pays one read per ``rounds``.

        Non-windowed batchers clamp ``rounds`` so the worst-case
        verify writes stay inside max_len (host-side arithmetic — no
        device read: pos = fill0 + len(tokens) - 1); when not even one
        round fits, falls back to spec_step's shrinking k_round. A
        windowed DRAFT batcher also falls back per round: its
        verify-then-commit ring discipline needs each round's
        acceptance before the next propose touches the ring. The
        clamped round count is quantized DOWN to a power of two:
        ``rounds`` is a static scan length, so every distinct value is
        its own XLA program — quantization bounds the program variants
        to log2(rounds) instead of one per tail length."""
        self._check_failed()
        self._refuse("speculate")
        k = max(2, int(k))
        if self._draft is not None and self.windowed:
            return self._spec_fallback_rounds(int(rounds), k, ngram)
        with self._step_lock:
            self._layout.advance_prefill(self)
            self._apply_pending()
            with self._lock:
                if not self._active.any():
                    return {}
                r = int(rounds)
                if not self.windowed:
                    pos_max = max(
                        req.fill0 + len(req.tokens) - 1
                        for s, req in enumerate(self._slots)
                        if req is not None and self._active[s]
                    )
                    r = min(r, (self.max_len - pos_max) // k)
                # NOT clamped by remaining budget: slots that exhaust
                # their budget mid-scan idle out ON DEVICE (active &=
                # budget > 0), exactly like step_pump's fixed n_steps.
                # Clamping here looked like a harmless economy but made
                # the STATIC scan length a function of live budgets —
                # so a warm-up drain compiled rounds=2/1 programs, the
                # measured drain then built rounds=4 inside the timed
                # region, and every budget tail recompiled its way down
                # a 4→2→1 program ladder. The only static clamp that
                # stays is write-room (cache-bounds correctness),
                # quantized so the window tail costs log2 variants, not
                # one per length.
                if r >= 1:
                    # room BEFORE the active snapshot: allocation may
                    # preempt (deactivate) a victim slot, and the
                    # launch/harvest must both see post-preemption state
                    self._layout.ensure_room(self, r * k)
                    while r & (r - 1):  # power-of-two floor (see above)
                        r &= r - 1
                    active_np = self._active.copy()
                    budget_dev, stop_dev, active_dev = (
                        self._pump_state_locked()
                    )
                    carried, fixed = self._layout.args(self)
                    args = (
                        self._tok, self._pos, active_dev, carried,
                        self._hist, budget_dev, stop_dev, self._temp,
                        self._topk, self._topp, self._keys, *fixed,
                    )
                    fn = (
                        self._spec_pump_sampling
                        if self._any_sampling_locked(active_np)
                        else self._spec_pump_greedy
                    )
            if r >= 1:
                try:
                    packed, tok, pos, act, carried, hist, budget = fn(
                        *args, rounds=r, k=k, g=int(ngram)
                    )
                    packed_np = np.asarray(packed)  # ONE transfer
                except Exception as exc:
                    self._mark_failed(exc)  # donated state consumed
                    raise
                acc, cols = int(packed_np[-2]), int(packed_np[-1])
                emits_np = packed_np[:-2].reshape(self.n_slots, r, k)
                with self._lock:
                    self._layout.commit(self, carried)
                    self._hist = self._pin(hist)
                    self._tok = self._pin(tok)
                    self._pos = self._pin(pos)
                    self._budget_dev = self._pin(budget)
                    self._active_dev = self._pin(act)
                    out, n_em = self._harvest_rows_locked(
                        active_np,
                        lambda s: (emits_np[s, rnd] for rnd in range(r)),
                    )
                    self._n_steps += r
                    self._n_tokens += n_em
                    self._n_spec_rounds += r
                    self._n_spec_accepted += acc
                    self._n_spec_columns += cols
                    return out
        # r < 1: no verify room at any width ≥ 2 — the shrinking-k host
        # round handles the tail tokens (takes _step_lock itself)
        return self._spec_fallback_rounds(1, k, ngram)

    def _spec_fallback_rounds(
        self, rounds: int, k: int, ngram: int
    ) -> Dict[int, List[int]]:
        """Drive ``rounds`` host spec_step rounds while preserving
        spec_pump's return contract ({rid: ALL tokens emitted}) —
        spec_step itself reports only the last token per request, so
        the full emission is reconstructed from req.tokens growth.
        Direct _Request references are captured the first time each rid
        is seen: re-resolving rids at the end through the bounded
        _done_pool would silently drop tokens for any request evicted by
        keep_results churn mid-rounds, breaking the ALL-tokens
        contract."""
        before: Dict[int, int] = {}
        with self._lock:
            for req in self._slots:
                if req is not None:
                    # floor 1: token 0 (the prefill's) is appended by
                    # _apply_pending — possibly DURING these
                    # rounds for a deferred admission — and is never
                    # pump output on the device paths either
                    before[req.rid] = max(1, len(req.tokens))
        default_start = 1
        refs: Dict[int, _Request] = {}
        emitted: set = set()
        for _ in range(int(rounds)):
            with self._lock:
                # pre-round snapshot: anything that can emit this round
                # is live in a slot (or pending) RIGHT NOW — grabbing the
                # reference here beats post-round _done_pool lookups,
                # which lose evicted requests
                for r in self._slots:
                    if r is not None and r.rid not in refs:
                        refs[r.rid] = r
                for p in self._pending:
                    if p.req.rid not in refs:
                        refs[p.req.rid] = p.req
            em = self.spec_step(k=k, ngram=ngram)
            if not em:
                break
            emitted |= set(em)
            missing = [rid for rid in em if rid not in refs]
            if missing:
                # admitted DURING the round (the round's own
                # _apply_pending, after our pre-round snapshot): resolve
                # now, while the request is still live or freshly done
                with self._lock:
                    live = {
                        r.rid: r for r in self._slots if r is not None
                    }
                    for rid in missing:
                        req = live.get(rid) or self._done_pool.get(rid)
                        if req is not None:
                            refs[rid] = req
        with self._lock:
            out = {
                rid: list(req.tokens[before.get(rid, default_start):])
                for rid, req in refs.items()
                if rid in emitted
            }
        return {rid: toks for rid, toks in out.items() if toks}

    def spec_step(self, k: int = 4, ngram: int = 2) -> Dict[int, int]:
        """One SPECULATIVE round: every active slot verifies k-1 guessed
        continuation tokens in one batched forward and commits its
        accepted prefix plus one correction/bonus token — several tokens
        per program launch when the guesses land. Proposals are
        prompt-lookup (n-gram) from each slot's own context (vLLM-style
        self-drafting: no draft model; models/speculative.py's scheme
        batched over slots).

        Works across the full serving matrix: greedy slots are EXACTLY
        equivalent to step() by construction (verification is the greedy
        model); sampling slots accept by point-mass rejection sampling
        against the same filtered distribution sample_tokens uses, so
        every emitted token is distributed exactly as a plain sampling
        step's (distribution-exact, not byte-identical — see
        spec_accept); windowed ring caches verify against the pre-write
        ring and commit only accepted columns (batched_windowed_verify /
        commit_ring_chunk), so rejected proposals never clobber window
        history; Pallas batchers speculate too — the verify forward uses
        inline XLA attention, so a generation mixing step() and
        spec_step() calls could diverge on near-tied logits (the kernel's
        accumulation order differs), but a server pumping spec_step
        exclusively (speculate=k) is self-consistent: every committed
        token is certified by the same verify program — when ngram
        lookup proposes nothing, a Pallas batcher runs a width-2
        all-sentinel verify (never acceptable, so it emits the plain
        step's token via the verify forward) instead of falling back to
        the kernel-certified plain step. The one remaining plain-step
        fallback on a Pallas batcher is a non-windowed batch whose
        tightest slot has room for <2 columns, i.e. the final token
        before max_len, where no verify chunk fits. XLA batchers fall
        back to a plain step whenever no slot has room for a chunk or no
        slot proposed anything (there the plain step and verify are the
        same inline-attention math). Returns {rid: last emitted token};
        use partials() for the full per-round stream."""
        self._check_failed()
        self._refuse("speculate")
        with self._step_lock:
            self._layout.advance_prefill(self)
            self._apply_pending()
            with self._lock:
                if not self._active.any():
                    return {}
                # before the active snapshot — may preempt a victim
                self._layout.ensure_room(self, int(k))
                active_np = self._active.copy()
                sampling = self._any_sampling_locked(active_np)
                pos_np = np.asarray(self._pos)
                if self.windowed:
                    # a ring has no end: the only bound is the window
                    k_round = max(1, min(k, self.max_len - 1))
                else:
                    room = min(
                        int(self.max_len - pos_np[s])
                        for s in range(self.n_slots) if active_np[s]
                    )
                    k_round = max(1, min(k, room))
                if k_round >= 2:
                    toks_host = np.zeros((self.n_slots, k_round), np.int32)
                    tok_np = np.asarray(self._tok)
                    toks_host[:, 0] = tok_np
                    if self._draft is None:
                        any_found = False
                        for s, req in enumerate(self._slots):
                            if req is None or not active_np[s]:
                                continue
                            ctx = np.concatenate(
                                [req.prompt,
                                 np.asarray(req.tokens, np.int32)]
                            )
                            cand = ngram_lookup(ctx, k_round - 1, ngram)
                            # -1 sentinel for found-nothing columns: a
                            # real greedy token (≥ 0) can never match
                            # it, so the acceptance scan stops at the
                            # pending token instead of crediting
                            # accidental token-0 hits (zero-fill is
                            # indistinguishable from proposing token 0);
                            # XLA's gather clamps the embed lookup
                            toks_host[s, 1:] = -1
                            if cand is not None and cand.size:
                                toks_host[s, 1 : 1 + cand.size] = cand
                                any_found = True
                        if not any_found:
                            if self._attn_impl == "pallas":
                                # a Pallas batcher must NOT mix a
                                # kernel-certified plain step into an
                                # exclusively-speculative generation
                                # (the kernel's accumulation order can
                                # diverge from verify on near-tied
                                # logits): run a width-2 all-sentinel
                                # verify instead — sentinels can never
                                # be accepted, so this emits exactly the
                                # plain step's token, certified by the
                                # same verify program as every other
                                # round
                                k_round = 2
                                toks_host = toks_host[:, :2]
                            else:
                                # no slot proposed anything: the verify
                                # forward would certify exactly one
                                # token per slot at k× the column cost —
                                # a plain step is the same result
                                # cheaper (and on XLA batchers it is
                                # bit-identical to verify)
                                k_round = 1
            if k_round < 2:
                # a pump of one, under the _step_lock already held
                # (outside self._lock — the pump reacquires it)
                return {
                    rid: t[-1] for rid, t in self._pump_locked(1).items()
                }
            if self._draft is not None:
                # k-1 batched draft forwards propose for every slot at
                # once; a draft always proposes, so there is no
                # found-nothing fallback. Safe outside self._lock: the
                # draft cache and per-slot device vectors are only
                # touched under _step_lock (held here) — submits may
                # queue pending inserts concurrently, but those join at
                # the next round's _apply_pending.
                toks_host[:, 1:] = self._draft.propose(
                    self._tok, self._pos, jnp.asarray(active_np), k_round
                )
            with self._lock:
                carried, fixed = self._layout.args(self)
            args = (
                jnp.asarray(toks_host), self._pos, jnp.asarray(active_np),
                carried, self._hist, self._temp, self._topk, self._topp,
                self._keys, *fixed,
            )
            round_fn = (
                self._spec_round_sampling if sampling
                else self._spec_round_greedy
            )
            try:
                m_dev, final_dev, carried, hist, pos2 = round_fn(*args)
                with self._lock:
                    # before the draft's own commit: the round carried
                    # (and donated) the draft cache too
                    self._layout.commit(self, carried)
                if self._draft is not None and self._draft.windowed:
                    # draft-side commit of the accepted columns (the ring
                    # discipline: nothing landed during propose)
                    self._draft.commit(args[1], m_dev, args[2])
                # [B] counts + [B] tokens — the only host transfers
                m_np = np.asarray(m_dev)
                final_np = np.asarray(final_dev)
            except Exception as exc:
                self._mark_failed(exc)  # donated state consumed
                raise
            with self._lock:
                self._hist = hist
                self._pos = self._pin(pos2)
                emitted: Dict[int, int] = {}
                new_tok = tok_np.copy()
                n_emitted = 0
                accepted = 0
                for s, req in enumerate(self._slots):
                    if req is None or not active_np[s]:
                        continue
                    m = int(m_np[s])
                    accepted += m - 1
                    planned = [int(t) for t in toks_host[s, 1:m]]
                    planned.append(int(final_np[s]))
                    for t in planned:
                        req.tokens.append(t)
                        emitted[req.rid] = t
                        n_emitted += 1
                        if req.finished():
                            break
                    new_tok[s] = req.tokens[-1]
                    if req.finished():
                        self._finish(s)
                self._tok = self._pin(jnp.asarray(new_tok))
                self._n_steps += 1
                self._n_tokens += n_emitted
                self._n_spec_rounds += 1
                self._n_spec_accepted += accepted
                # count only columns actually holding proposals — -1
                # sentinel columns (ngram found-nothing fill) can never
                # be accepted, so crediting them would bias the
                # per-proposal acceptance rate (and llm_serve's
                # speculate=auto EMA built on it) low
                self._n_spec_columns += int(
                    (toks_host[active_np, 1:] >= 0).sum()
                )
                self._pump_state_dirty = True  # host-stepped path
                return emitted

    def stats(self) -> Dict[str, float]:
        """Serving counters — the token-world analogue of the filter
        element's latency/throughput props (tensor_filter.c:334-433):
        cumulative steps/tokens, decode rate, and current occupancy."""
        with self._lock:
            occupied = sum(r is not None for r in self._slots)
            st = {
                "steps": self._n_steps,
                "tokens_emitted": self._n_tokens,
                "tokens_per_step": (
                    self._n_tokens / self._n_steps if self._n_steps else 0.0
                ),
                "spec_rounds": self._n_spec_rounds,
                "spec_accepted_tokens": self._n_spec_accepted,
                # accepted/columns is the true per-proposal acceptance
                # rate whatever the slot occupancy or k was per round
                # (sentinel found-nothing columns count in neither)
                "spec_columns": self._n_spec_columns,
                "spec_acceptance_rate": (
                    self._n_spec_accepted / self._n_spec_columns
                    if self._n_spec_columns else 0.0
                ),
                # one launch of the admit program per _apply_pending
                # that had rows to splice, whatever their number
                "admit_launches": self._n_admit_launches,
                "admitted": self._n_admitted,
                "slots_occupied": occupied,
                "slots_free": self.n_slots - occupied,
                "results_pending_pickup": len(self._done_pool),
                "prefixes_registered": len(
                    self._prefixes_paged if self._paged else self._prefixes
                ),
            }
            if self._paged:
                st.update(self._pool.stats())
                st["kv_block_size"] = self.block_size
                st["kv_prefill_queue"] = len(self._prefill_q)
                st["kv_preemptions"] = self._slo.preemptions_total
                # the decode attention that serves it, as resolved at
                # construction (an unset attn_impl and a registry
                # refusal both land here): "pallas" | "xla"
                st["attn_impl"] = self._attn_impl
                st["kv_migrations_out"] = self._n_migrations_out
                st["kv_migrations_in"] = self._n_migrations_in
                # bucket programs launched, and the pumps that launched
                # at least one: their quotient is buckets a prefilling pump
                st["kv_prefill_chunks"] = self._n_prefill_chunk_programs
                st["prefill_pumps"] = self._n_prefill_pumps
                # prompt programs of any kind and the prompts whose prefill
                # they completed (their quotient is prompts a program: 1
                # where nothing is packed, under 1 where prompts are
                # chunked), and the programs that held a bin
                st["prefill_programs"] = self._n_prefill_programs
                st["prefill_prompts"] = self._n_prefill_prompts
                st["prefill_packed_programs"] = (
                    self._n_prefill_packed_programs
                )
                st["request_resumes"] = self._n_resumes
            st["family"] = self._family.name
            for k, v in self._aux_totals.items():
                st[self._family.aux_prefix + k] = v
            return st

    def _pin(self, x):
        """Keep per-slot vectors on their mesh sharding after eager
        updates, so the compiled step sees stable input shardings."""
        return jax.device_put(x, self._vec_sh) if self._vec_sh else x

    def _finish(self, slot: int) -> None:
        req = self._slots[slot]
        req.done = True
        self._active[slot] = False
        self._pump_state_dirty = True  # slot left the batch
        if self._paged:
            # release the request's blocks (shared prefix blocks drop a
            # reference and stay adoptable in the pool's cached tier)
            self._pool.free(
                self._tables[slot, : self._n_alloc[slot]].tolist()
            )
            self._tables[slot] = 0
            self._n_alloc[slot] = 0
            self._tables_dirty = True
        self._slo.finished(req.rid, len(req.tokens))
        self._done_pool[req.rid] = req
        while len(self._done_pool) > self._keep_results:
            self._done_pool.popitem(last=False)  # evict oldest uncollected
        self._slots[slot] = None

    def result(self, rid: int) -> Optional[List[int]]:
        """Completed token list for ``rid``, or None if still running."""
        with self._lock:
            if rid in self._done_pool:
                return list(self._done_pool[rid].tokens)
            return None

    def partial(self, rid: int) -> Optional[List[int]]:
        """Tokens emitted SO FAR for ``rid`` (running or finished) — the
        token-streaming read surface. None for unknown/evicted ids."""
        return self.partials([rid]).get(rid)

    def partials(self, rids) -> Dict[int, List[int]]:
        """Batched partial(): {rid: tokens-so-far} for every known rid,
        in ONE lock acquisition and one pass over slots/pending/done —
        the per-token streaming hot path polls every pending request per
        decode step, so the per-rid scan must not multiply."""
        want = set(rids)
        out: Dict[int, List[int]] = {}
        with self._lock:
            for req in self._slots:
                if req is not None and req.rid in want:
                    out[req.rid] = list(req.tokens)
            for p in self._pending:
                if p.req.rid in want:
                    out[p.req.rid] = list(p.req.tokens)
            for rid in want - out.keys():
                if rid in self._done_pool:
                    out[rid] = list(self._done_pool[rid].tokens)
        return out

    def requests(self) -> Dict[int, Dict[str, Any]]:
        """Per-request SLO/state view — the data behind
        ``nns-top --requests``: state (queued/prefilling/decoding/done),
        blocks held (paged), queue/TTFT/TPOT latencies and deadline
        headroom, from the SLO ledger."""
        with self._lock:
            extra: Dict[int, Dict[str, Any]] = {}
            for s, req in enumerate(self._slots):
                if req is None:
                    continue
                row: Dict[str, Any] = {
                    "slot": s, "tokens": len(req.tokens),
                }
                if self._paged:
                    row["blocks"] = int(self._n_alloc[s])
                extra[req.rid] = row
            return self._slo.view(extra)

    # -- warm restart (the PR-7 drain→snapshot→restore discipline) ---------
    def snapshot(self) -> dict:
        """Serializable serving state: every live request, the device
        state (cache or block arena, per-slot vectors, token history)
        and — paged — block tables, pool accounting and the SLO ledger.
        Pending admissions are applied first; a paged batcher must have
        drained its prefill queue (pump until ``kv_prefill_queue`` is 0)
        so no half-staged prompt is lost."""
        self._check_failed()
        self._refuse("snapshot")
        with self._step_lock:
            self._apply_pending()
            with self._lock:
                if self._paged and self._prefill_q:
                    raise RuntimeError(
                        "snapshot with queued prefills: pump until the "
                        "prefill queue drains first"
                    )
                reqs = []
                for s, req in enumerate(self._slots):
                    if req is None:
                        continue
                    reqs.append({
                        "slot": s,
                        "rid": req.rid,
                        "budget": req.budget,
                        "temperature": req.temperature,
                        "top_k": req.top_k,
                        "top_p": req.top_p,
                        "stop_token": req.stop_token,
                        "key": np.asarray(req.key).tolist(),
                        "prompt": np.asarray(req.prompt).tolist(),
                        "tokens": list(req.tokens),
                        "fill0": req.fill0,
                        "active": bool(self._active[s]),
                    })
                snap = {
                    "layout": "paged" if self._paged else "slot",
                    "n_slots": self.n_slots,
                    "max_len": self.max_len,
                    "requests": reqs,
                    "device": jax.tree_util.tree_map(np.asarray, {
                        "cache": self._cache,
                        "tok": self._tok,
                        "pos": self._pos,
                        "temp": self._temp,
                        "topk": self._topk,
                        "topp": self._topp,
                        "keys": self._keys,
                        "hist": self._hist,
                    }),
                    "next_rid": self._next_rid,
                    "counters": {
                        "n_steps": self._n_steps,
                        "n_tokens": self._n_tokens,
                        "n_spec_rounds": self._n_spec_rounds,
                        "n_spec_accepted": self._n_spec_accepted,
                        "n_spec_columns": self._n_spec_columns,
                    },
                    "done": {
                        rid: list(r.tokens)
                        for rid, r in self._done_pool.items()
                    },
                    "slo": self._slo.snapshot(),
                }
                if self._paged:
                    snap["tables"] = self._tables.copy()
                    snap["n_alloc"] = self._n_alloc.copy()
                    snap["pool"] = self._pool.snapshot()
                    snap["prefixes"] = {
                        pid: (tok.tolist(), list(blks))
                        for pid, (tok, blks)
                        in self._prefixes_paged.items()
                    }
                else:
                    # slot layout: registered prefixes live as staged
                    # K/V tuples — they must survive the restart too, or
                    # restored callers holding a pid get ValueError (and
                    # a reset _next_prefix would recycle their ids)
                    snap["prefixes"] = {
                        pid: {
                            "kv": jax.tree_util.tree_map(
                                np.asarray, stored
                            ),
                            "plen": int(pl),
                            "tokens": np.asarray(tok).tolist(),
                        }
                        for pid, (stored, pl, tok)
                        in self._prefixes.items()
                    }
                snap["next_prefix"] = self._next_prefix
                return snap

    def restore(self, snap: dict) -> None:
        """Load a :meth:`snapshot` into a freshly built batcher of the
        SAME configuration: decoding continues exactly where the
        snapshot stopped (same streams, same block tables, same prefix
        index — the remembered sharing survives the restart)."""
        self._refuse("snapshot")
        want = "paged" if self._paged else "slot"
        if snap.get("layout") != want:
            raise ValueError(
                f"snapshot layout {snap.get('layout')!r} does not match "
                f"this batcher's {want!r}"
            )
        if (snap.get("n_slots") != self.n_slots
                or snap.get("max_len") != self.max_len):
            raise ValueError("snapshot geometry mismatch")
        if self._paged:
            # refuse a shrunk pool BEFORE any device state moves: the
            # first mutation below donates the arena, so discovering the
            # mismatch inside pool.restore() would leave a corrupt half-
            # restored batcher. PoolCapacityError names what the
            # snapshot could shed (cached prefix blocks, registered
            # prefix pins) to fit a smaller kv_blocks on re-snapshot.
            from nnstreamer_tpu.kv.blocks import PoolCapacityError
            psnap = snap.get("pool", {})
            snap_blocks = int(psnap.get("n_blocks", self._pool.n_blocks))
            if snap_blocks > self._pool.n_blocks:
                refcount = list(psnap.get("refcount", []))
                live = sum(1 for rc in refcount[1:] if rc > 0)
                evictable = [
                    ("cached-block", int(b))
                    for b in psnap.get("cached", [])
                ] + [
                    ("prefix", int(pid), len(blks))
                    for pid, (_tok, blks)
                    in snap.get("prefixes", {}).items()
                ]
                raise PoolCapacityError(
                    f"snapshot was taken with kv_blocks={snap_blocks} "
                    f"({live} in use) but this batcher has only "
                    f"{self._pool.n_blocks}: restore refused before any "
                    f"state moved; {len(evictable)} evictable "
                    "candidates (cached prefix blocks / registered "
                    "prefixes) could be shed at the source to fit",
                    needed=snap_blocks, have=self._pool.n_blocks,
                    evictable=evictable,
                )
        with self._step_lock, self._lock:
            dev = snap["device"]
            self._cache = jax.tree_util.tree_map(
                jnp.asarray, dev["cache"]
            )
            self._tok = self._pin(jnp.asarray(dev["tok"]))
            self._pos = self._pin(jnp.asarray(dev["pos"]))
            self._temp = self._pin(jnp.asarray(dev["temp"]))
            self._topk = self._pin(jnp.asarray(dev["topk"]))
            self._topp = self._pin(jnp.asarray(dev["topp"]))
            self._keys = self._pin(jnp.asarray(dev["keys"]))
            self._hist = self._pin(jnp.asarray(dev["hist"]))
            self._slots = [None] * self.n_slots
            self._active = np.zeros((self.n_slots,), bool)
            for d in snap["requests"]:
                req = _Request(
                    d["rid"], d["budget"],
                    temperature=d["temperature"], top_k=d["top_k"],
                    top_p=d["top_p"], stop_token=d["stop_token"],
                    key=np.asarray(d["key"], np.uint32),
                    prompt=np.asarray(d["prompt"], np.int32),
                )
                req.tokens = list(d["tokens"])
                req.fill0 = int(d["fill0"])
                self._slots[d["slot"]] = req
                self._active[d["slot"]] = bool(d["active"])
            self._next_rid = int(snap["next_rid"])
            c = snap.get("counters", {})
            self._n_steps = int(c.get("n_steps", 0))
            self._n_tokens = int(c.get("n_tokens", 0))
            self._n_spec_rounds = int(c.get("n_spec_rounds", 0))
            self._n_spec_accepted = int(c.get("n_spec_accepted", 0))
            self._n_spec_columns = int(c.get("n_spec_columns", 0))
            self._done_pool = OrderedDict()
            for rid, toks in snap.get("done", {}).items():
                stub = _Request(int(rid), 0)
                stub.tokens = list(toks)
                stub.done = True
                self._done_pool[int(rid)] = stub
            self._slo.restore(snap.get("slo", {}))
            if self._paged:
                self._tables = np.asarray(snap["tables"], np.int32).copy()
                self._n_alloc = np.asarray(
                    snap["n_alloc"], np.int32
                ).copy()
                self._tables_dirty = True
                self._pool.restore(snap["pool"])
                self._prefixes_paged = {
                    int(pid): (np.asarray(tok, np.int32), list(blks))
                    for pid, (tok, blks)
                    in snap.get("prefixes", {}).items()
                }
            else:
                self._prefixes = {
                    int(pid): (
                        jax.tree_util.tree_map(jnp.asarray, d["kv"]),
                        int(d["plen"]),
                        np.asarray(d["tokens"], np.int32),
                    )
                    for pid, d in snap.get("prefixes", {}).items()
                }
            self._next_prefix = int(
                snap.get("next_prefix", self._next_prefix)
            )
            self._pump_state_dirty = True

    @property
    def n_free(self) -> int:
        with self._lock:
            return sum(r is None for r in self._slots)
