"""Plain reference of the decoder block the benchmark's configurations state.

Straightforward ``jax.numpy``: pre-norm RMSNorm (eps 1e-6, unit weights at
initialisation), fused q|k|v projection, rotary embedding over half-split
head dims (theta 10000), grouped-query causal attention scaled by
1/sqrt(head_dim) after the contraction, SiLU-gated FFN, final RMSNorm, untied
output head. No cache, no batching tricks, no kernels.

It imports nothing of the program and takes nothing the program has made.
The weights are what the configuration's ``seed:<n>`` means: the recipe
below (normal(0, 1/sqrt(fan_in)) dense weights, normal(0, 0.02) embedding,
keys split as written) is the configuration's statement of its weights, and
each layer's weights are drawn from their keys inside the layer loop, so the
reference never holds more than one layer and fits beside anything.

The precision is the configuration's to state (its ``reference`` block names
one of ``PRECISIONS``), and the control is the same mathematics in the
nearest precision below it:

  float32           true float32 everywhere (the CPU rehearsal sizes)
  bf16_operands     what olmo-1b and mistral-7b state: float32 storage,
                    residual, norms, softmax and accumulation; every
                    contraction (dense matmuls, QK^T, PV, the head) rounds
                    both operands to bfloat16 and accumulates in float32,
                    which is what the TPU's default precision does to
                    float32 operands
  bf16_activations  the control: weights, residual stream and every
                    activation held in bfloat16 (the batcher's
                    ``compute_dtype=bfloat16``), the step below float32 that
                    a later PR would be tempted by
  int8_weights      a further control: bf16_activations with every dense
                    weight, the embedding and the head rounded to int8 with
                    one scale per output feature (``quantize:int8w``)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

THETA = 10000.0
EPS = 1e-6

# name -> (operand dtype of every contraction, dtype the weights, residual
#          and activations are held in, int8 weights)
PRECISIONS = {
    "float32": (jnp.float32, jnp.float32, False),
    "bf16_operands": (jnp.bfloat16, jnp.float32, False),
    "bf16_activations": (jnp.bfloat16, jnp.bfloat16, False),
    "int8_weights": (jnp.bfloat16, jnp.bfloat16, True),
}


def weight_keys(seed: int, n_layers: int):
    """The key schedule of ``seed:<n>``: eight subkeys of PRNGKey(seed);
    the first five are split per layer (wqkv, wo, w_gate, w_up, w_down),
    then embedding, then head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    per_layer = jnp.stack([jax.random.split(ks[i], n_layers) for i in range(5)], 1)
    return per_layer, ks[5], ks[6]  # [L, 5, 2], embed key, head key


def _int8(w):
    """Symmetric int8 with one scale per output feature, dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _dense(key, cin, cout, held, quant):
    w = jax.random.normal(key, (cin, cout), jnp.float32) * math.sqrt(1.0 / cin)
    return (_int8(w) if quant else w).astype(held)


def _contract(spec, a, b, op, held):
    """One contraction as the precision states it: operands rounded to
    ``op``, exact products, float32 accumulation, result held in ``held``."""
    return jnp.einsum(spec, a.astype(op), b.astype(op), precision="highest",
                      preferred_element_type=jnp.float32).astype(held)


def _rmsnorm(x):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + EPS)
    return y.astype(x.dtype)


def _rope(x, positions):
    half = x.shape[-1] // 2
    freqs = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[None, :, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _layer_weights(keys, *, d, heads, kv_heads, d_ff, precision):
    _, held, quant = PRECISIONS[precision]
    hd = d // heads
    return (_dense(keys[0], d, d + 2 * kv_heads * hd, held, quant),   # wqkv
            _dense(keys[1], d, d, held, quant),                       # wo
            _dense(keys[2], d, d_ff, held, quant),                    # w_gate
            _dense(keys[3], d, d_ff, held, quant),                    # w_up
            _dense(keys[4], d_ff, d, held, quant))                    # w_down


def _block(x, weights, *, d, heads, kv_heads, precision):
    op, held, _ = PRECISIONS[precision]
    mm = functools.partial(_contract, op=op, held=held)
    wqkv, wo, w_gate, w_up, w_down = weights
    b, t, _ = x.shape
    hd = d // heads
    pos = jnp.arange(t)
    qkv = mm("btd,de->bte", _rmsnorm(x), wqkv)
    q = _rope(qkv[..., :d].reshape(b, t, heads, hd), pos)
    k, v = jnp.split(qkv[..., d:], 2, axis=-1)
    k = _rope(k.reshape(b, t, kv_heads, hd), pos)
    v = v.reshape(b, t, kv_heads, hd)
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd)
    s = _contract("btkgd,bskd->bkgts", q, k, op, jnp.float32) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(held)
    o = mm("bkgts,bskd->btkgd", p, v).reshape(b, t, d)
    x = x + mm("btd,de->bte", o, wo)
    y = _rmsnorm(x)
    ffn = jax.nn.silu(mm("btd,df->btf", y, w_gate)) * mm("btd,df->btf", y, w_up)
    return x + mm("btf,fd->btd", ffn, w_down)


@functools.partial(
    jax.jit,
    static_argnames=("d", "heads", "kv_heads", "d_ff", "vocab", "precision", "rows"),
)
def _score(tokens, want, per_layer, embed_key, head_key, *, d, heads, kv_heads,
           d_ff, vocab, precision, rows):
    op, held, quant = PRECISIONS[precision]
    shape = dict(d=d, heads=heads, kv_heads=kv_heads, precision=precision)
    embed = jax.random.normal(embed_key, (vocab, d), jnp.float32) * 0.02
    if quant:
        embed = _int8(embed)
    n, t = tokens.shape
    x = embed[tokens].astype(held).reshape(n // rows, rows, t, d)

    def layer(x, keys):  # one layer's weights drawn once, used block of rows by block
        w = _layer_weights(keys, d_ff=d_ff, **shape)
        return jax.lax.map(lambda xb: _block(xb, w, **shape), x), None

    x, _ = jax.lax.scan(layer, x, per_layer)
    head = _dense(head_key, d, vocab, held, quant)

    def read(args):
        xb, wb = args
        z = _contract("btd,dv->btv", _rmsnorm(xb), head, op, jnp.float32)
        return (z.max(-1), z.argmax(-1).astype(jnp.int32),
                jnp.take_along_axis(z, wb[..., None], -1)[..., 0])

    best, first, at_want = jax.lax.map(read, (x, want.reshape(n // rows, rows, t)))
    return best.reshape(n, t), first.reshape(n, t), at_want.reshape(n, t)


def score(sizes: dict, seed: int, tokens, want, precision: str, rows: int = 8):
    """One forward of the model the configuration states (``sizes``: d_model,
    n_heads, n_kv_heads, n_layers, d_ff, vocab) with the weights of ``seed``,
    in one of ``PRECISIONS``, over tokens [N, T] int32 (N a multiple of
    ``rows``, the block the layers are applied to at a time). For every
    position, whose logits predict the next token: the best logit, the token
    that has it, and the logit of ``want`` [N, T] there, each [N, T]. T is
    whatever the caller padded to: attention is causal, so trailing padding
    changes nothing before it."""
    per_layer, ek, hk = weight_keys(seed, sizes["n_layers"])
    return _score(jnp.asarray(tokens, jnp.int32), jnp.asarray(want, jnp.int32),
                  per_layer, ek, hk, d=sizes["d_model"], heads=sizes["n_heads"],
                  kv_heads=sizes["n_kv_heads"], d_ff=sizes["d_ff"],
                  vocab=sizes["vocab"], precision=precision, rows=rows)
