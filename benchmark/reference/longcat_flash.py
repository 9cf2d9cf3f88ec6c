"""Plain reference of LongCat-Flash's shortcut-connected double layer, for one
chip's share of the routed experts.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision("highest")``:
no cache, no absorbed attention, no sorting, no kernels. One layer is

    for i in (0, 1):
        a = norm(h);  h = h + MLA_i(a)
        b = norm(h)
        if i == 0:  s = MoE(b)         # the shortcut: added after the second FFN
        h = h + FFN_i(b)               # down(silu(gate b) * up b)
    h = h + s

MLA (latent attention) at position t, H heads: ``c_q = norm(a W_qa) sqrt(d/q_rank)``;
per head ``[q_nope | q_rope] = c_q W_qb``; ``[c | k_r] = a W_kva``;
``c = norm(c) sqrt(d/kv_rank)``; RoPE (half-split pairing) on q_rope and on k_r,
which all heads share; per head ``[k_nope | v] = c W_kvb``; score
``(q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope)``, causal softmax in float32,
``o = (sum p v) W_o``. K and V are expanded for every position (un-absorbed).

MoE: ``p = softmax(b W_r)`` over all ``n_routed + n_zero`` router outputs, in
float32 at highest precision whatever the precision of the rest; the top
``moe_topk`` of ``p + bias`` are chosen, their weights are ``scale * p``
(unbiased, not renormalised). Outputs below ``n_routed`` are SiLU-gated
experts, the rest are identity ("zero-compute") experts:
``MoE(b) = sum_{k routed, held here} w_k E_k(b) + (sum_{k zero} w_k) b``. This chip holds
experts ``[expert_offset, expert_offset + n_held)``; what the absent experts
would have added is left out. Experts are computed by a loop over the held
experts, every token through every one, masked by its gate.

It imports nothing of the program. The weights are what ``seed:<n>`` means:
the recipe of ``weight_key`` / ``STD`` below (float32 normal draws, one key per
tensor, layer by layer, a routed expert's key from its index among ALL routed
experts so that every share of a layer draws the same expert alike), rounded
once to the dtype a precision holds them in. ``score`` gets the sizes
``benchmark/lib/shapes.sizes_of`` knows; what they lack (ranks, head dims,
expert widths and counts, the share held) is read from the configuration file
under ``benchmark/configs/`` that names this module and has those sizes.

Precisions (the configuration's ``reference`` block names one):

  float32           true float32 everywhere (the CPU rehearsal and unit tests)
  bf16_operands     what longcat-flash-chat states: weights and latent cache
                    stored in bfloat16; residual, norms, softmax and router in
                    float32; every other contraction on bfloat16 operands with
                    float32 accumulation. Rounding a weight once for storage
                    and rounding it at each contraction are the same values.
  bf16_activations  the control: the residual stream and every activation
                    held in bfloat16
  int8_weights      a further control: bf16_activations with every dense,
                    expert, embedding and head weight rounded to int8, one
                    scale per output feature
"""

from __future__ import annotations

import functools
import glob
import json
import math
import os

import jax
import jax.numpy as jnp

# name -> (operand dtype of every contraction but the router's, dtype the
#          residual and activations are held in, int8 weights)
PRECISIONS = {
    "float32": (jnp.float32, jnp.float32, False),
    "bf16_operands": (jnp.bfloat16, jnp.float32, False),
    "bf16_activations": (jnp.bfloat16, jnp.bfloat16, False),
    "int8_weights": (jnp.bfloat16, jnp.bfloat16, True),
}

# tensor ids of the key schedule; sublayer i adds 100 * i
WQA, WQB, WKVA, WKVB, WO, FFN_GATE, FFN_UP, FFN_DOWN = 1, 2, 3, 4, 5, 6, 7, 8
ROUTER, ROUTER_BIAS, EXP_GATE, EXP_UP, EXP_DOWN = 20, 21, 30, 31, 32
EMBED, HEAD, LAYERS = 1, 2, 3
EMBED_STD = 0.02
ROUTER_GAIN = 2.0      # router logits ~ N(0, 2^2): the top 12 of 768 carry ~0.4 of p
ROUTER_BIAS_STD = 1e-3  # small against the spread of p (the 12th pick is ~2e-2)

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def shape_of(sizes: dict) -> dict:
    """The configuration file that names this module and has ``sizes``'s
    widths, depth and vocabulary, reduced to what the forward needs."""
    for path in sorted(glob.glob(os.path.join(_CONFIGS, "*.json"))):
        with open(path) as f:
            c = json.load(f)
        if c.get("reference", {}).get("module") != "longcat_flash":
            continue
        if (c["hidden_size"], c["num_attention_heads"], c["num_layers"],
                c["ffn_hidden_size"], c["vocab_size"]) == (
                sizes["d_model"], sizes["n_heads"], sizes["n_layers"],
                sizes["d_ff"], sizes["vocab"]):
            return shape_from_config(c)
    raise SystemExit(f"no benchmark/configs/*.json names reference longcat_flash "
                     f"with the sizes {sizes}")


def shape_from_config(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "heads": c["num_attention_heads"],
        "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v_dim": c["v_head_dim"], "d_ff": c["ffn_hidden_size"],
        "d_expert": c["expert_ffn_hidden_size"],
        "n_routed": c.get("reduced_from", {}).get("n_routed_experts",
                                                  c["n_routed_experts"]),
        "n_zero": c["zero_expert_num"], "topk": c["moe_topk"],
        "scale": float(c["routed_scaling_factor"]),
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "n_layers": c["num_layers"], "vocab": c["vocab_size"],
        "n_held": c["n_routed_experts"], "expert_offset": c.get("expert_offset", 0),
    }


def weight_key(seed: int, layer=None, tensor: int = 0, sub: int = 0, expert=None):
    """The key of one tensor of ``seed:<n>``. Embedding and head:
    ``fold_in(PRNGKey(seed), EMBED | HEAD)``. A layer's tensor:
    ``fold_in(fold_in(fold_in(PRNGKey(seed), LAYERS), layer), tensor + 100 * sub)``,
    and a routed expert's folds its index among all routed experts in last."""
    key = jax.random.PRNGKey(seed)
    if layer is None:
        return jax.random.fold_in(key, tensor)
    key = jax.random.fold_in(jax.random.fold_in(key, LAYERS), layer)
    key = jax.random.fold_in(key, tensor + 100 * sub)
    return key if expert is None else jax.random.fold_in(key, expert)


def stds(s: dict) -> dict:
    """Standard deviation of every tensor's draw: 1/sqrt(fan_in), with W_qb and
    W_kvb divided by the latent's published multiplier so that queries, keys
    and values have unit scale and the softmax a temperature of about one."""
    d = s["d"]
    return {
        WQA: d ** -0.5, WQB: s["q_rank"] ** -0.5 / math.sqrt(d / s["q_rank"]),
        WKVA: d ** -0.5, WKVB: s["kv_rank"] ** -0.5 / math.sqrt(d / s["kv_rank"]),
        WO: (s["heads"] * s["v_dim"]) ** -0.5,
        FFN_GATE: d ** -0.5, FFN_UP: d ** -0.5, FFN_DOWN: s["d_ff"] ** -0.5,
        ROUTER: ROUTER_GAIN * d ** -0.5, ROUTER_BIAS: ROUTER_BIAS_STD,
        EXP_GATE: d ** -0.5, EXP_UP: d ** -0.5, EXP_DOWN: s["d_expert"] ** -0.5,
    }


def _int8(w):
    """Symmetric int8 with one scale per output feature, dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _draw(key, shape, std, op, quant):
    w = jax.random.normal(key, shape, jnp.float32) * std
    return (_int8(w) if quant else w).astype(op)


def _contract(spec, a, b, op, held):
    """One contraction as the precision states it: operands rounded to
    ``op``, exact products, float32 accumulation, result held in ``held``."""
    return jnp.einsum(spec, a.astype(op), b.astype(op), precision="highest",
                      preferred_element_type=jnp.float32).astype(held)


def _rmsnorm(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


def _rope(x, positions, theta):
    """x [..., T, heads, D] rotated in half-split pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def mla_weights(s, seed_key, sub, op, quant):
    """(W_qa, W_qb, W_kva, W_kvb, W_o) of sublayer ``sub``; ``seed_key(tensor,
    sub)`` gives each tensor's key."""
    sd = stds(s)
    h = s["heads"]
    shapes = {WQA: (s["d"], s["q_rank"]),
              WQB: (s["q_rank"], h * (s["nope"] + s["rope"])),
              WKVA: (s["d"], s["kv_rank"] + s["rope"]),
              WKVB: (s["kv_rank"], h * (s["nope"] + s["v_dim"])),
              WO: (h * s["v_dim"], s["d"])}
    return tuple(_draw(seed_key(t, sub), shapes[t], sd[t], op, quant)
                 for t in (WQA, WQB, WKVA, WKVB, WO))


def ffn_weights(s, seed_key, sub, op, quant):
    sd = stds(s)
    shapes = {FFN_GATE: (s["d"], s["d_ff"]), FFN_UP: (s["d"], s["d_ff"]),
              FFN_DOWN: (s["d_ff"], s["d"])}
    return tuple(_draw(seed_key(t, sub), shapes[t], sd[t], op, quant)
                 for t in (FFN_GATE, FFN_UP, FFN_DOWN))


def mla(a, w, s, op, held):
    """Latent attention over a block ``a`` [B, T, d], un-absorbed."""
    wqa, wqb, wkva, wkvb, wo = w
    mm = functools.partial(_contract, op=op, held=held)
    b, t, d = a.shape
    h, dn, dr, dv = s["heads"], s["nope"], s["rope"], s["v_dim"]
    pos = jnp.arange(t)
    cq = _rmsnorm(mm("btd,dr->btr", a, wqa), s["eps"]) * math.sqrt(d / s["q_rank"])
    q = mm("btr,re->bte", cq.astype(held), wqb).reshape(b, t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, s["theta"])
    ckr = mm("btd,de->bte", a, wkva)
    c = _rmsnorm(ckr[..., :s["kv_rank"]], s["eps"]) * math.sqrt(d / s["kv_rank"])
    k_r = _rope(ckr[..., s["kv_rank"]:][:, :, None, :], pos, s["theta"])[:, :, 0]
    kv = mm("btr,re->bte", c.astype(held), wkvb).reshape(b, t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    sc = (_contract("bthd,bshd->bhts", q_nope, k_nope, op, jnp.float32)
          + _contract("bthd,bsd->bhts", q_rope, k_r, op, jnp.float32))
    sc = sc / math.sqrt(dn + dr)
    sc = jnp.where((pos[:, None] >= pos[None, :])[None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1).astype(held)
    o = mm("bhts,bshd->bthd", p, v).reshape(b, t, h * dv)
    return mm("bte,ed->btd", o, wo)


def ffn(b, w, op, held):
    mm = functools.partial(_contract, op=op, held=held)
    w_gate, w_up, w_down = w
    return mm("btf,fd->btd",
              jax.nn.silu(mm("btd,df->btf", b, w_gate)) * mm("btd,df->btf", b, w_up),
              w_down)


def route(b, s, seed_key):
    """-> gates [B, T, n_routed + n_zero] float32: ``scale * p`` at the chosen
    outputs, zero elsewhere. The router runs in float32 at highest precision
    in every precision, as published."""
    sd = stds(s)
    n_out = s["n_routed"] + s["n_zero"]
    w_r = jax.random.normal(seed_key(ROUTER, 0), (s["d"], n_out), jnp.float32) * sd[ROUTER]
    bias = jax.random.normal(seed_key(ROUTER_BIAS, 0), (n_out,), jnp.float32) * sd[ROUTER_BIAS]
    logits = jnp.einsum("btd,dr->btr", b.astype(jnp.float32), w_r, precision="highest")
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(p + bias, s["topk"])
    chosen = jax.nn.one_hot(idx, n_out, dtype=jnp.float32).sum(-2)  # distinct picks
    return chosen * p * s["scale"]


def expert_weights(s, seed_key, expert, op, quant):
    sd = stds(s)
    d, f = s["d"], s["d_expert"]
    return (_draw(seed_key(EXP_GATE, 0, expert), (d, f), sd[EXP_GATE], op, quant),
            _draw(seed_key(EXP_UP, 0, expert), (d, f), sd[EXP_UP], op, quant),
            _draw(seed_key(EXP_DOWN, 0, expert), (f, d), sd[EXP_DOWN], op, quant))


def layer(x, s, seed: int, index, precision: str, n_held=None, expert_offset=None):
    """One shortcut-connected double layer over x [R, rows, T, d] (``R``
    blocks of ``rows`` sequences, applied a block at a time), for the share
    ``[expert_offset, expert_offset + n_held)`` of the routed experts (the
    configuration's by default)."""
    op, held, quant = PRECISIONS[precision]
    n_held = s["n_held"] if n_held is None else n_held
    e0 = s["expert_offset"] if expert_offset is None else expert_offset

    def seed_key(tensor, sub, expert=None):
        return weight_key(seed, index, tensor, sub, expert)

    eps = s["eps"]
    w_mla = mla_weights(s, seed_key, 0, op, quant)
    x = jax.lax.map(lambda xb: xb + mla(_rmsnorm(xb, eps), w_mla, s, op, held), x)

    def shortcut(xb):  # the identity experts' part, and the held experts' gates
        b = _rmsnorm(xb, eps)
        g = route(b, s, seed_key)
        ident = g[..., s["n_routed"]:].sum(-1)
        local = jax.lax.dynamic_slice_in_dim(g, e0, max(n_held, 1), axis=-1)
        return (ident[..., None] * b.astype(jnp.float32)).astype(held), local

    sc, gates = jax.lax.map(shortcut, x)

    def one_expert(j, sc):
        w = expert_weights(s, seed_key, e0 + j, op, quant)

        def add(args):
            xb, sb, gb = args
            y = ffn(_rmsnorm(xb, eps), w, op, held)
            gate = jax.lax.dynamic_index_in_dim(gb, j, axis=-1, keepdims=True)
            return (sb.astype(jnp.float32) + gate * y.astype(jnp.float32)).astype(held)

        return jax.lax.map(add, (x, sc, gates))

    sc = jax.lax.fori_loop(0, n_held, one_expert, sc)
    w_ffn = ffn_weights(s, seed_key, 0, op, quant)
    x = jax.lax.map(lambda xb: xb + ffn(_rmsnorm(xb, eps), w_ffn, op, held), x)
    w_mla = mla_weights(s, seed_key, 1, op, quant)
    x = jax.lax.map(lambda xb: xb + mla(_rmsnorm(xb, eps), w_mla, s, op, held), x)
    w_ffn = ffn_weights(s, seed_key, 1, op, quant)
    x = jax.lax.map(lambda xb: xb + ffn(_rmsnorm(xb, eps), w_ffn, op, held), x)
    return x + sc


def _frozen(s: dict):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("shape", "precision", "rows"))
def _forward(tokens, seed, *, shape, precision, rows):
    """tokens [N, T] -> final-normed hidden [N/rows, rows, T, d] and the head."""
    s = dict(shape)
    op, held, quant = PRECISIONS[precision]
    embed = jax.random.normal(weight_key(seed, None, EMBED), (s["vocab"], s["d"]),
                              jnp.float32) * EMBED_STD
    if quant:
        embed = _int8(embed)
    n, t = tokens.shape
    x = embed.astype(op)[tokens].astype(held).reshape(n // rows, rows, t, s["d"])
    x = jax.lax.fori_loop(
        0, s["n_layers"], lambda i, x: layer(x, s, seed, i, precision), x)
    head = _draw(weight_key(seed, None, HEAD), (s["d"], s["vocab"]),
                 s["d"] ** -0.5, op, quant)
    return x, head


@functools.partial(jax.jit, static_argnames=("shape", "precision", "rows"))
def _score(tokens, want, seed, *, shape, precision, rows):
    s = dict(shape)
    op = PRECISIONS[precision][0]
    x, head = _forward(tokens, seed, shape=shape, precision=precision, rows=rows)
    n, t = tokens.shape

    def read(args):
        xb, wb = args
        z = _contract("btd,dv->btv", _rmsnorm(xb, s["eps"]), head, op, jnp.float32)
        return (z.max(-1), z.argmax(-1).astype(jnp.int32),
                jnp.take_along_axis(z, wb[..., None], -1)[..., 0])

    best, first, at_want = jax.lax.map(read, (x, want.reshape(n // rows, rows, t)))
    return best.reshape(n, t), first.reshape(n, t), at_want.reshape(n, t)


def logits(shape: dict, seed: int, tokens, precision: str = "float32"):
    """Full forward, logits [N, T, vocab] float32 (small sizes: the unit tests)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x, head = _forward(tokens, jnp.asarray(seed, jnp.int32), shape=_frozen(shape),
                       precision=precision, rows=1)
    op = PRECISIONS[precision][0]
    z = _contract("rbtd,dv->rbtv", _rmsnorm(x, shape["eps"]), head, op, jnp.float32)
    return z.reshape(tokens.shape + (shape["vocab"],))


def score(sizes: dict, seed: int, tokens, want, precision: str, rows: int = 1,
          block: int = 16):
    """One forward of the model the configuration states with the weights of
    ``seed``, in one of ``PRECISIONS``, over tokens [N, T] int32, ``block``
    requests at a time (so that 64 x 2,048 tokens of hidden state and a
    layer's weights fit one chip beside each other), attention over ``rows``
    of them at a time. For every position, whose logits predict the next
    token: the best logit, the token that has it, and the logit of ``want``
    [N, T] there, each [N, T]. Attention is causal, so trailing padding
    changes nothing before it."""
    shape = _frozen(shape_of(sizes))
    tokens = jnp.asarray(tokens, jnp.int32)
    want = jnp.asarray(want, jnp.int32)
    block = min(block, tokens.shape[0])
    outs = [_score(tokens[i:i + block], want[i:i + block], jnp.asarray(seed, jnp.int32),
                   shape=shape, precision=precision, rows=rows)
            for i in range(0, tokens.shape[0], block)]
    return tuple(jnp.concatenate(col, 0) for col in zip(*outs))
