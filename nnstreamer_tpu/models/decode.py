"""KV-cache autoregressive decoding for the transformer LM family.

Streaming token generation is this framework's native ground (the
reference's recurrent analogue is tensor_repo feedback loops holding RNN
state across frames, tests/nnstreamer_repo_{rnn,lstm}): the KV cache is the
in-pipeline state, and both prefill and the per-token step are single XLA
programs with static shapes — the decode loop is a ``lax.scan`` over a
fixed budget, so generation jit-compiles once.

Layout: cache k/v are [L, B, max_len, H, Dh]; a scalar ``pos`` tracks the
fill level. Attention at each step runs over the full max_len with a
``<= pos`` mask (fixed shape; masked positions cost FLOPs but keep XLA
static — the standard TPU serving trade).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models import transformer as tfm


def init_cache(
    params: Dict, batch: int, max_len: int, n_heads: int, dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array]:
    """Zeroed (k, v) cache [L, B, max_len, KV, Dh] (KV < H under GQA)."""
    L, d = params["blocks"]["ln1"].shape
    hd = d // n_heads
    kv = tfm.n_kv_heads_of(params["blocks"]["wqkv"], d, n_heads)
    shape = (L, batch, max_len, kv, hd)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def prefill(
    params: Dict,
    tokens,
    n_heads: int,
    max_len: int,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
):
    """Run the prompt through the model once, filling the cache.

    tokens [B, T] (T ≤ max_len) → (logits [B, T, V], (cache_k, cache_v),
    pos=T)."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} > max_len {max_len}")
    x = tfm.embed_lookup(params["embed"], tokens, compute_dtype)
    positions = jnp.arange(t)
    x, (ks, vs) = tfm.apply_layers(
        params["blocks"], x, n_heads, positions, ffn_fn=ffn_fn, return_kv=True
    )
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    pad = max_len - t
    cache_k = jnp.pad(
        ks.astype(compute_dtype), ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
    )
    cache_v = jnp.pad(
        vs.astype(compute_dtype), ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
    )
    return logits, (cache_k, cache_v), jnp.asarray(t, jnp.int32)


def segment_attention(segment):
    """The ``attn_fn`` of a bucket that holds several prompts: row i
    attends row j iff both are rows of the same prompt
    (``segment[i] == segment[j] >= 0``) and ``j <= i``. The formulation is
    ``dense_attention``'s with that mask for its causal one (masked scores
    are NEG_INF before the softmax in both), so a prompt's rows see what
    they would see alone in the bucket, up to summation order."""
    row = jnp.arange(segment.shape[0])
    mask = (
        (segment[:, None] == segment[None, :])
        & (segment[:, None] >= 0)
        & (row[None, :] <= row[:, None])
    )

    def attn(q, k, v, causal: bool = True):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        s = jnp.where(mask[None, None], s, tfm.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    return attn


def prefill_packed(
    params: Dict,
    tokens,
    positions,
    segment,
    last,
    n_heads: int,
    compute_dtype=jnp.float32,
):
    """Several prompts through the model in ONE bucket.

    tokens [1, P]: the prompts laid end to end (the batcher starts each on
    a multiple of its block size), ``positions`` [P] each row's position
    inside its own prompt (what RoPE rotates by), ``segment`` [P] int32
    the prompt a row belongs to (-1 on padding rows), ``last`` [K] the row
    of each prompt's last token (-1 where the bucket holds fewer) →
    (logits [K, V] f32, (cache_k, cache_v) [L, 1, P, KV, Dh]): the K/V of
    every row as ``prefill`` would leave them for that prompt alone, and
    the final norm and vocabulary head over the K gathered rows only."""
    x = tfm.embed_lookup(params["embed"], tokens, compute_dtype)
    x, (ks, vs) = tfm.apply_layers(
        params["blocks"], x, n_heads, positions,
        attn_fn=segment_attention(segment), return_kv=True,
    )
    rows = jnp.take(x[0], jnp.maximum(last, 0), axis=0)  # [K, D]
    rows = tfm.rmsnorm(rows, params["ln_f"])
    logits = (rows @ tfm.wt(params["head"], rows.dtype)).astype(jnp.float32)
    return logits, (ks.astype(compute_dtype), vs.astype(compute_dtype))


def decode_step(
    params: Dict,
    token,
    pos,
    cache: Tuple[jax.Array, jax.Array],
    n_heads: int,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
):
    """One token in, one distribution out.

    token [B] int32, pos scalar (number of tokens already cached) →
    (logits [B, V], cache', pos+1). This is exactly verify_chunk with a
    1-token chunk — one shared body keeps the plain and speculative
    decode paths identical by construction."""
    logits, cache, _ = verify_chunk(
        params, token[:, None], pos, cache, n_heads, ffn_fn, compute_dtype
    )
    return logits[:, 0], cache, pos + 1


def verify_chunk(
    params: Dict,
    tokens,
    pos,
    cache: Tuple[jax.Array, jax.Array],
    n_heads: int,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
    return_logits: bool = True,
):
    """Score a k-token candidate chunk in ONE forward against the cache.

    tokens [B, k] int32 (candidates, e.g. a draft model's proposals), pos
    scalar (tokens already cached) → (logits [B, k, V] f32, cache', pos+k).
    Query i sits at absolute position pos+i and attends cache positions
    ≤ pos+i (causal within the chunk). The chunk's K/V are written at
    pos..pos+k-1; the caller rolls back rejected tokens by simply using a
    smaller ``pos`` afterwards — positions beyond the accepted point are
    overwritten before any mask can reach them (the same invariant the
    continuous batcher relies on). This is the speculative-decoding
    verify step (models/speculative.py).

    Precondition: pos + k ≤ max_len — dynamic_update_slice would clamp
    the start index and silently overwrite certified earlier positions.
    Checked here whenever ``pos`` is concrete (outside a trace)."""
    cache_k, cache_v = cache
    max_len = cache_k.shape[2]
    b, kk_len = tokens.shape
    if not isinstance(pos, jax.core.Tracer) and int(pos) + kk_len > max_len:
        raise ValueError(
            f"verify_chunk: pos({int(pos)}) + k({kk_len}) > max_len"
            f"({max_len}); KV cache would clamp and corrupt"
        )
    x = tfm.embed_lookup(params["embed"], tokens, compute_dtype)  # [B,k,D]
    positions = pos + jnp.arange(kk_len, dtype=jnp.int32)

    def body(carry, layer):
        x = carry
        blk, ck, cv = layer
        q, k, v = tfm.block_qkv(x, blk, n_heads, positions)  # k/v [B,k,KV,Dh]
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, pos, 0, 0))
        mask = (
            jnp.arange(max_len)[None, :] <= positions[:, None]
        )  # [k, max_len]
        o = tfm.cache_attention(q, ck, cv, mask[None])
        o = o.astype(x.dtype).reshape(b, kk_len, -1)
        x = x + o @ tfm.wt(blk["wo"], x.dtype)
        x = tfm.block_ffn(x, blk, ffn_fn)
        return x, (ck, cv)

    x, (cache_k, cache_v) = jax.lax.scan(
        body, x, (params["blocks"], cache_k, cache_v)
    )
    if not return_logits:
        # cache-advance only (chunked prefill's non-final buckets): skip
        # the ln_f + vocab-sized head projection, which dominates a
        # short chunk's FLOPs
        return None, (cache_k, cache_v), pos + kk_len
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    return logits, (cache_k, cache_v), pos + kk_len


def windowed_chunk(
    params: Dict,
    tokens,
    pos,
    valid_n,
    cache: Tuple[jax.Array, jax.Array],
    n_heads: int,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
    return_logits: bool = True,
):
    """Advance a RING cache by one chunk with EXACT sliding-window
    semantics (windowed chunked prefill; Mistral-style rolling prefill).

    ``cache`` k/v are rings [L, B, W, KV, Dh] over the last W tokens (the
    layout batched_decode_step(windowed=True) consumes). tokens [B, k]
    start at absolute position ``pos``; only the first ``valid_n`` rows
    are real (the tail is pad — its writes are suppressed so live ring
    entries are never clobbered, and causal masking keeps it out of every
    valid query's key set). Returns (logits [B,k,V] or None, cache',
    pos + valid_n).

    Exactness: query i (absolute p = pos+i) must attend the previous
    W-1 tokens and itself — including ring entries the chunk itself is
    about to overwrite. So attention runs against the PRE-write ring
    concatenated with the chunk's fresh K/V, and the ring is updated
    after: ring slot s last held absolute position pos-1-d where
    d = (wp-1-s) mod W (wp = pos % W), attendable by query i iff written
    (d ≤ pos-1) and in-window (d ≤ W-2-i).

    Precondition: wp + k ≤ W — no mid-chunk ring wrap. Callers align
    chunk starts to bucket strides with W % bucket == 0 (checked when
    ``pos`` is concrete)."""
    cache_k, cache_v = cache
    W = cache_k.shape[2]
    b, k_len = tokens.shape
    if not isinstance(pos, jax.core.Tracer) and int(pos) % W + k_len > W:
        raise ValueError(
            f"windowed_chunk: chunk [{int(pos)}, {int(pos) + k_len}) wraps "
            f"the W={W} ring mid-chunk; align chunk starts to a bucket "
            "size that divides the window"
        )
    pos = jnp.asarray(pos, jnp.int32)
    valid_n = jnp.asarray(valid_n, jnp.int32)
    wp = pos % W
    x = tfm.embed_lookup(params["embed"], tokens, compute_dtype)
    positions = pos + jnp.arange(k_len, dtype=jnp.int32)
    row = jnp.arange(k_len, dtype=jnp.int32)
    d = (wp - 1 - jnp.arange(W, dtype=jnp.int32)) % W  # [W] steps behind
    ring_mask = d[None, :] <= jnp.minimum(pos - 1, W - 2 - row)[:, None]
    chunk_mask = row[None, :] <= row[:, None]  # causal (pad rows are
    # later rows, so no valid query ever attends one)
    mask = jnp.concatenate([ring_mask, chunk_mask], axis=1)  # [k, W+k]
    keep = (row < valid_n)[None, :, None, None]

    def body(carry, layer):
        x = carry
        blk, ck, cv = layer
        q, k, v = tfm.block_qkv(x, blk, n_heads, positions)
        o = tfm.cache_attention(
            q,
            jnp.concatenate([ck, k.astype(ck.dtype)], axis=1),
            jnp.concatenate([cv, v.astype(cv.dtype)], axis=1),
            mask[None],
        )
        # write the chunk into the ring (contiguous by precondition),
        # blending so pad rows keep the pre-chunk entries
        tail = ck.shape[2:]
        old_k = jax.lax.dynamic_slice(ck, (0, wp, 0, 0), (b, k_len) + tail)
        old_v = jax.lax.dynamic_slice(cv, (0, wp, 0, 0), (b, k_len) + tail)
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.where(keep, k.astype(ck.dtype), old_k), (0, wp, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.where(keep, v.astype(cv.dtype), old_v), (0, wp, 0, 0)
        )
        o = o.astype(x.dtype).reshape(b, k_len, -1)
        x = x + o @ tfm.wt(blk["wo"], x.dtype)
        x = tfm.block_ffn(x, blk, ffn_fn)
        return x, (ck, cv)

    x, (cache_k, cache_v) = jax.lax.scan(
        body, x, (params["blocks"], cache_k, cache_v)
    )
    if not return_logits:
        return None, (cache_k, cache_v), pos + valid_n
    x = tfm.rmsnorm(x, params["ln_f"])
    logits = (x @ tfm.wt(params["head"], x.dtype)).astype(jnp.float32)
    return logits, (cache_k, cache_v), pos + valid_n


def beam_search(
    params: Dict,
    prompt,
    n_heads: int,
    max_new_tokens: int,
    beam_width: int = 4,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
):
    """Beam search over the KV-cache decode path.

    prompt [1, T] int32 → (tokens [1, max_new_tokens] int32 of the best
    beam, its total log-prob). The beams ARE the cache batch dim: one
    batched decode_step serves all beams per step, and beam reordering is
    a gather on the cache's slot axis — the same fixed-shape machinery as
    everything else, scanned over the token budget so the whole search is
    one compiled program. All beams decode the full budget (no EOS
    stopping), so scores compare directly; beam_width=1 reduces exactly
    to greedy generate()."""
    prompt = jnp.asarray(prompt, jnp.int32)
    b, t = prompt.shape
    if b != 1:
        raise ValueError("beam_search serves one stream (B=1)")
    W = beam_width
    max_len = t + max_new_tokens
    V = params["head"]["scale"].shape[-1] if isinstance(
        params["head"], dict
    ) else params["head"].shape[-1]

    logits, (ck, cv), pos = prefill(
        params, prompt, n_heads, max_len, ffn_fn, compute_dtype
    )
    # replicate the prompt cache across W beams
    ck = jnp.repeat(ck, W, axis=1)
    cv = jnp.repeat(cv, W, axis=1)
    lp0 = jax.nn.log_softmax(logits[0, -1])
    top0 = jax.lax.top_k(lp0, W)
    tok = top0[1].astype(jnp.int32)          # [W]
    scores = top0[0]                         # [W]

    def step(carry, _):
        tok, scores, ck, cv, pos = carry
        logits, (ck, cv), pos = decode_step(
            params, tok, pos, (ck, cv), n_heads, ffn_fn, compute_dtype
        )
        lp = jax.nn.log_softmax(logits, axis=-1)       # [W, V]
        cand = scores[:, None] + lp                    # [W, V]
        flat_scores, flat_idx = jax.lax.top_k(cand.reshape(-1), W)
        beam_idx = (flat_idx // V).astype(jnp.int32)   # parent beam
        tok = (flat_idx % V).astype(jnp.int32)
        # reorder the caches to follow the surviving beams
        ck = jnp.take(ck, beam_idx, axis=1)
        cv = jnp.take(cv, beam_idx, axis=1)
        return (tok, flat_scores, ck, cv, pos), (tok, beam_idx)

    (tok, scores, *_), (toks, parents) = jax.lax.scan(
        step, (tok, scores, ck, cv, pos), None, length=max_new_tokens - 1
    )

    # backtrack the best beam through the parent pointers (host side)
    toks = np.asarray(toks)          # [steps, W]
    parents = np.asarray(parents)    # [steps, W]
    scores = np.asarray(scores)
    beam = int(scores.argmax())
    seq = []
    for i in range(toks.shape[0] - 1, -1, -1):
        seq.append(int(toks[i, beam]))
        beam = int(parents[i, beam])
    seq.append(int(np.asarray(top0[1])[beam]))
    seq.reverse()
    return (
        jnp.asarray(np.asarray(seq, np.int32))[None, :],
        float(scores.max()),
    )


def generate(
    params: Dict,
    prompt,
    n_heads: int,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    ffn_fn: Optional[Callable] = None,
    compute_dtype=jnp.float32,
):
    """Greedy (temperature=0) or sampled generation.

    prompt [B, T] int32 → tokens [B, max_new_tokens] int32. One prefill
    program + one scanned decode program; both compile once per shape."""
    b, t = prompt.shape
    max_len = max_len or (t + max_new_tokens)
    if max_len < t + max_new_tokens:
        # Too-small caches don't error downstream: dynamic_update_slice
        # clamps the write index, silently overwriting the last slot.
        raise ValueError(
            f"max_len={max_len} < prompt_len({t}) + max_new_tokens"
            f"({max_new_tokens}); KV cache would overflow"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    logits, cache, pos = prefill(
        params, prompt, n_heads, max_len, ffn_fn, compute_dtype
    )
    last = logits[:, -1]

    def pick(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(
            jnp.int32
        )

    def step(carry, key):
        last_logits, cache, pos = carry
        tok = pick(last_logits, key)
        logits, cache, pos = decode_step(
            params, tok, pos, cache, n_heads, ffn_fn, compute_dtype
        )
        return (logits, cache, pos), tok

    keys = jax.random.split(rng, max_new_tokens)
    _, toks = jax.lax.scan(step, (last, cache, pos), keys)
    return toks.T  # [B, max_new_tokens]
