"""Plain reference of Granite-4.0-H: Mamba-2 state-space layers beside
grouped-query attention layers without positions, every layer followed by
softmax-routed experts with a shared MLP, for one chip's share of the routed
experts.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision("highest")``:
no cache, no chunked scan, no sorting, no kernels. ``x_0 = embedding_multiplier
E[token]``; layer l of 0..n_layers-1, ``x`` the residual, every norm an RMSNorm
(eps 1e-5), ``r`` the ``residual_multiplier``::

    x = x + r Mix_l(norm(x));  h = norm(x);  x = x + r (Experts(h) + SharedMLP(h))

``Mix_l`` is attention where ``layer_types[l]`` says so, Mamba-2 elsewhere.
``logits = norm(x) E^T / logits_scaling``: the head is the embedding.

Mamba-2 (H heads of P = ``mamba_d_head`` channels, N = ``mamba_d_state``, one
group; per head)::

    [z, u, dt] = a W_in                      widths H P, H P + 2 N, H
    u = SiLU(conv4(u) + b_conv);  [x~, B, C] = u        B, C shared by all heads
    D_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) D_t)
    S_t = a_t S_{t-1} + D_t x~_t B_t^T,  S_0 = 0        S in R^{P x N}
    y_t = S_t C_t + D x~_t
    out = W_out [ RMSNorm_{H P}( y * SiLU(z) ) ]        the gate first, then one norm

``conv4`` is a causal depthwise convolution over the last 4 positions; the scan
runs token by token in float32 in every precision. No positions anywhere.

Attention: ``q, k, v = a W_q, a W_k, a W_v`` (heads / K/V heads of ``hidden_size
/ num_attention_heads``), no rotation, causal softmax of ``q.k
attention_multiplier`` in float32, ``W_o``.

Expert layer (every layer): ``l = h W_r`` over all ``n_routed`` outputs, float32
at highest precision whatever the precision of the rest; the ``topk`` largest
are chosen; their weights are a softmax over THOSE logits; ``y = SharedMLP(h) +
sum_{chosen, held here} w_e E_e(h)``, each a SiLU-gated MLP. This chip holds
experts ``[expert_offset, expert_offset + n_held)``; what the absent experts
would have added is left out. Experts are computed by a loop over the held
experts, every token through every one, masked by its gate.

It imports nothing of the program. The weights are what ``seed:<n>`` means: the
recipe of ``weight_key`` / ``matrix_shapes`` below (float32 draws, one key per
tensor, layer by layer, a routed expert's key from its index among ALL routed
experts), rounded once to the dtype a precision holds them in. ``score`` gets
the sizes ``benchmark/lib/shapes.sizes_of`` knows; the rest is read from the
configuration file under ``benchmark/configs/`` that names this module and has
those sizes.

Precisions (the configuration's ``reference`` block names one):

  float32           true float32 everywhere (the CPU rehearsal and unit tests)
  bf16_operands     what granite-4.0-h-small states: weights, keys and values
                    and convolution inputs stored in bfloat16; residual, norms,
                    softmax, router, dt, decay and the state in float32; every
                    other contraction on bfloat16 operands with float32
                    accumulation
  bf16_activations  the control: the residual stream and every activation held
                    in bfloat16 (the state stays float32)
  int8_weights      a further control: bf16_activations with every matrix
                    rounded to int8, one scale per output feature
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

# tensor ids of the key schedule
S_WZ, S_WXBC, S_WDT, S_CONV, S_CONV_B, S_A_LOG, S_DT_BIAS, S_WOUT = range(1, 9)
A_WQ, A_WK, A_WV, A_WO = 20, 21, 22, 23
ROUTER, EXP_GATE, EXP_UP, EXP_DOWN = 40, 50, 51, 52
SH_GATE, SH_UP, SH_DOWN = 60, 61, 62
EMBED, LAYERS = 1, 3
# the head is the embedding: at the usual 0.02 the input token's own logit
# (12 d std^2 / rms(x)) is 15 sigmas of the other logits above them, every
# position predicts its own token and no comparison could fail
EMBED_STD = 1e-3
A_RANGE = (1.0, 16.0)    # A = exp(A_log) ~ U(1, 16)
DT_RANGE = (1e-3, 1e-1)  # softplus(dt_bias) ~ exp(U(log 1e-3, log 1e-1))

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def shape_of(sizes: dict) -> dict:
    """The configuration file that names this module and has ``sizes``'s
    widths, depth and vocabulary, reduced to what the forward needs."""
    for path in sorted(glob.glob(os.path.join(_CONFIGS, "*.json"))):
        with open(path) as f:
            c = json.load(f)
        if c.get("reference", {}).get("module") != "granite_hybrid":
            continue
        if (c["hidden_size"], c["num_attention_heads"], c["num_hidden_layers"],
                c["intermediate_size"], c["vocab_size"]) == (
                sizes["d_model"], sizes["n_heads"], sizes["n_layers"],
                sizes["d_ff"], sizes["vocab"]):
            return shape_from_config(c)
    raise SystemExit(f"no benchmark/configs/*.json names reference granite_hybrid "
                     f"with the sizes {sizes}")


def shape_from_config(c: dict) -> dict:
    n_layers = c["num_hidden_layers"]
    return {
        "d": c["hidden_size"], "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "ssm_heads": c["mamba_n_heads"], "ssm_head_dim": c["mamba_d_head"],
        "ssm_state": c["mamba_d_state"], "conv": c["mamba_d_conv"],
        "attn_layers": tuple(i for i, kind in enumerate(c["layer_types"][:n_layers])
                             if kind == "attention"),
        "d_expert": c["intermediate_size"], "d_shared": c["shared_intermediate_size"],
        "n_routed": c.get("reduced_from", {}).get("num_local_experts",
                                                  c["num_local_experts"]),
        "topk": c["num_experts_per_tok"],
        "eps": float(c["rms_norm_eps"]),
        "embed_mult": float(c["embedding_multiplier"]),
        "resid_mult": float(c["residual_multiplier"]),
        "attn_scale": float(c["attention_multiplier"]),
        "logit_scale": float(c["logits_scaling"]),
        "n_layers": n_layers, "vocab": c["vocab_size"],
        "n_held": c["num_local_experts"], "expert_offset": c.get("expert_offset", 0),
    }


def weight_key(seed, layer=None, tensor: int = 0, expert=None):
    """The key of one tensor of ``seed:<n>``. The embedding (which is the
    head): ``fold_in(PRNGKey(seed), EMBED)``. Tensor ``t`` of layer l (0-based):
    ``fold_in(fold_in(fold_in(PRNGKey(seed), LAYERS), l), t)``, and a routed
    expert's folds its index among all routed experts in last."""
    key = jax.random.PRNGKey(seed)
    if layer is None:
        return jax.random.fold_in(key, tensor)
    key = jax.random.fold_in(jax.random.fold_in(key, LAYERS), layer)
    key = jax.random.fold_in(key, tensor)
    return key if expert is None else jax.random.fold_in(key, expert)


def matrix_shapes(s: dict) -> dict:
    """tensor id -> (fan_in, fan_out) of every matrix drawn normal(0, 1/fan_in)."""
    d, hd = s["d"], s["head_dim"]
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    width = inner + 2 * s["ssm_state"]
    return {
        S_WZ: (d, inner), S_WXBC: (d, width), S_WDT: (d, s["ssm_heads"]),
        S_CONV: (s["conv"], width), S_CONV_B: (s["conv"], width),  # the bias: one row
        S_WOUT: (inner, d),
        A_WQ: (d, s["heads"] * hd), A_WK: (d, s["kv_heads"] * hd),
        A_WV: (d, s["kv_heads"] * hd), A_WO: (s["heads"] * hd, d),
        ROUTER: (d, s["n_routed"]),
        EXP_GATE: (d, s["d_expert"]), EXP_UP: (d, s["d_expert"]),
        EXP_DOWN: (s["d_expert"], d),
        SH_GATE: (d, s["d_shared"]), SH_UP: (d, s["d_shared"]),
        SH_DOWN: (s["d_shared"], d),
    }


def _int8(w):
    """Symmetric int8 with one scale per output feature, dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _matrix(s, key, tensor, op, quant):
    """One matrix: normal(0, 1/fan_in) in float32, rounded once to ``op``.
    The convolution's bias is one row of its fan_in (the 4 taps)."""
    fan_in, fan_out = matrix_shapes(s)[tensor]
    shape = (fan_out,) if tensor == S_CONV_B else (fan_in, fan_out)
    w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return (_int8(w) if quant and w.ndim == 2 else w).astype(op)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def scan_vectors(s, key_of):
    """(A_log, dt_bias, D), each [H], float32 in every precision: ``A_log =
    log u``, ``u ~ U(1, 16)``; ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))``; ``D = 1``."""
    h = s["ssm_heads"]
    a_log = jnp.log(_uniform(key_of(S_A_LOG), (h,), *A_RANGE))
    dt = jnp.exp(_uniform(key_of(S_DT_BIAS), (h,),
                          math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
    return a_log, dt + jnp.log(-jnp.expm1(-dt)), jnp.ones((h,), jnp.float32)


def _rounded(x, dtype):
    """x in float32, rounded to what ``dtype`` holds. For bfloat16 an explicit
    ``reduce_precision``: a convert to bfloat16 and back is a rounding the TPU
    compiler may drop (``xla_allow_excess_precision``), which would make a
    stated precision, and every control, more exact than it says."""
    x = x.astype(jnp.float32)
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _hold(x, held):
    """x held in ``held``: rounded for certain, then stored."""
    return _rounded(x, held).astype(held)


def _contract(spec, a, b, op, held):
    """One contraction as the precision states it: operands rounded to
    ``op``, exact products, float32 accumulation, result held in ``held``."""
    return _hold(jnp.einsum(spec, a.astype(op), b.astype(op), precision="highest",
                            preferred_element_type=jnp.float32), held)


def _rmsnorm(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return _hold(y, x.dtype)


def _conv4(u, w, bias, op, held):
    """Causal depthwise convolution over the last ``taps`` positions plus its
    bias: u [B, T, C], w [taps, C]; inputs, taps and bias rounded to ``op``
    (the inputs are what the served model keeps between steps), products
    summed in float32."""
    taps, t = w.shape[0], u.shape[1]
    up = jnp.pad(_rounded(u, op), ((0, 0), (taps - 1, 0), (0, 0)))
    w32 = _rounded(w, op)
    y = sum(up[:, i:i + t] * w32[i] for i in range(taps)) + _rounded(bias, op)
    return _hold(y, held)


SSM_MATRICES = (S_WZ, S_WXBC, S_WDT, S_CONV, S_CONV_B, S_WOUT)
ATTN_MATRICES = (A_WQ, A_WK, A_WV, A_WO)


def mixer_weights(s, key_of, op, quant, attention: bool) -> dict:
    """tensor id -> tensor of one mixer (drawn once a layer, outside the loop
    over blocks)."""
    w = {t: _matrix(s, key_of(t), t, op, quant)
         for t in (ATTN_MATRICES if attention else SSM_MATRICES)}
    if not attention:
        w[S_A_LOG], w[S_DT_BIAS], w["D"] = scan_vectors(s, key_of)
    return w


def mamba2(a, wts, s, op, held):
    """The Mamba-2 mixer over a block ``a`` [B, T, d], token by token."""
    mm = functools.partial(_contract, op=op, held=held)
    w = wts.__getitem__
    b, t, _ = a.shape
    h, p, n = s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    z = mm("btd,dc->btc", a, w(S_WZ)).astype(jnp.float32)
    u = _conv4(mm("btd,dc->btc", a, w(S_WXBC)), w(S_CONV), w(S_CONV_B), op, held)
    u = _hold(jax.nn.silu(u.astype(jnp.float32)), held).astype(jnp.float32)
    x = u[..., :h * p].reshape(b, t, h, p)
    bm, cm = u[..., h * p:h * p + n], u[..., h * p + n:]
    dt = jax.nn.softplus(mm("btd,dh->bth", a, w(S_WDT)).astype(jnp.float32)
                         + w(S_DT_BIAS))
    decay = jnp.exp(-jnp.exp(w(S_A_LOG)) * dt)

    def step(S, xs):
        x_t, b_t, c_t, d_t, a_t = xs          # [B,H,P] [B,N] [B,N] [B,H] [B,H]
        S = (a_t[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return S, jnp.einsum("bhpn,bn->bhp", S, c_t, precision="highest")

    seq = tuple(jnp.moveaxis(m, 1, 0) for m in (x, bm, cm, dt, decay))
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1) + w("D")[:, None] * x                 # [B, T, H, P]
    y = _hold(y.reshape(b, t, h * p) * jax.nn.silu(z), held)
    return mm("btc,cd->btd", _rmsnorm(y, s["eps"]), w(S_WOUT))


def attention(a, wts, s, op, held):
    """Grouped-query attention without positions over a block ``a`` [B, T, d]."""
    mm = functools.partial(_contract, op=op, held=held)
    w = wts.__getitem__
    b, t, _ = a.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    q = mm("btd,de->bte", a, w(A_WQ)).reshape(b, t, kv, h // kv, hd)
    k = mm("btd,de->bte", a, w(A_WK)).reshape(b, t, kv, hd)
    v = mm("btd,de->bte", a, w(A_WV)).reshape(b, t, kv, hd)
    sc = _contract("btkgd,bskd->bkgts", q, k, op, jnp.float32) * s["attn_scale"]
    sc = jnp.where((pos[:, None] >= pos[None, :])[None, None, None], sc, -1e30)
    p = _hold(jax.nn.softmax(sc, axis=-1), held)
    o = mm("bkgts,bskd->btkgd", p, v).reshape(b, t, h * hd)
    return mm("bte,ed->btd", o, w(A_WO))


def ffn(b, w, op, held):
    mm = functools.partial(_contract, op=op, held=held)
    w_gate, w_up, w_down = w
    return mm("btf,fd->btd",
              jax.nn.silu(mm("btd,df->btf", b, w_gate)) * mm("btd,df->btf", b, w_up),
              w_down)


def router_weights(s, key_of):
    """W_r, float32 in every precision."""
    shape = matrix_shapes(s)[ROUTER]
    return jax.random.normal(key_of(ROUTER), shape, jnp.float32) * shape[0] ** -0.5


def route(b, w_r, s):
    """-> gates [B, T, n_routed] float32: at the chosen outputs the softmax
    over the chosen logits, zero elsewhere. The router runs in float32 at
    highest precision in every precision."""
    logits = jnp.einsum("btd,dr->btr", b.astype(jnp.float32), w_r,
                        precision="highest")
    picked, idx = jax.lax.top_k(logits, s["topk"])
    weights = jax.nn.softmax(picked, axis=-1)
    return (jax.nn.one_hot(idx, s["n_routed"], dtype=jnp.float32)
            * weights[..., None]).sum(-2)


def expert_layer(x, s, key_of, op, held, quant, n_held=None, expert_offset=None,
                 shared: bool = True):
    """The expert layer's share over normed blocks x [R, rows, T, d] -> what it
    adds before the residual multiplier: the shared MLP (where ``shared``) and
    the held experts' gated outputs."""
    n_held = s["n_held"] if n_held is None else n_held
    e0 = s["expert_offset"] if expert_offset is None else expert_offset

    w_shared = tuple(_matrix(s, key_of(t), t, op, quant)
                     for t in (SH_GATE, SH_UP, SH_DOWN)) if shared else None
    w_r = router_weights(s, key_of)

    def start(xb):
        g = route(xb, w_r, s)
        local = jax.lax.dynamic_slice_in_dim(g, e0, max(n_held, 1), axis=-1)
        if shared:
            return ffn(xb, w_shared, op, held), local
        return jnp.zeros_like(xb), local

    acc, gates = jax.lax.map(start, x)

    def one_expert(j, acc):
        w = tuple(_matrix(s, key_of(t, e0 + j), t, op, quant)
                  for t in (EXP_GATE, EXP_UP, EXP_DOWN))

        def add(args):
            xb, ab, gb = args
            gate = jax.lax.dynamic_index_in_dim(gb, j, axis=-1, keepdims=True)
            y = ffn(xb, w, op, held)
            return _hold(ab.astype(jnp.float32) + gate * y.astype(jnp.float32), held)

        return jax.lax.map(add, (x, acc, gates))

    return jax.lax.fori_loop(0, n_held, one_expert, acc)


def layer(x, s, seed, index: int, precision: str):
    """Layer ``index`` (0-based, static: the layers differ in kind) over x
    [R, rows, T, d], a block of ``rows`` sequences at a time."""
    op, held, quant = PRECISIONS[precision]

    def key_of(tensor, expert=None):
        return weight_key(seed, index, tensor, expert)

    eps, r = s["eps"], s["resid_mult"]
    is_attn = index in s["attn_layers"]
    mix = attention if is_attn else mamba2
    wts = mixer_weights(s, key_of, op, quant, is_attn)

    def add(xb, yb):
        return _hold(xb.astype(jnp.float32) + r * yb.astype(jnp.float32), held)

    x = jax.lax.map(lambda xb: add(xb, mix(_rmsnorm(xb, eps), wts, s, op, held)), x)
    normed = jax.lax.map(lambda xb: _rmsnorm(xb, eps), x)
    return add(x, expert_layer(normed, s, key_of, op, held, quant))


def _frozen(s: dict):
    return tuple(sorted(s.items()))


def _embedding(s, seed, op, quant):
    embed = jax.random.normal(weight_key(seed, None, EMBED), (s["vocab"], s["d"]),
                              jnp.float32) * EMBED_STD
    return (_int8(embed) if quant else embed).astype(op)


@functools.partial(jax.jit, static_argnames=("shape", "precision", "rows"))
def _forward(tokens, seed, *, shape, precision, rows):
    """tokens [N, T] -> hidden before the final norm [N/rows, rows, T, d] and
    the embedding, which is the head."""
    s = dict(shape)
    op, held, quant = PRECISIONS[precision]
    embed = _embedding(s, seed, op, quant)
    n, t = tokens.shape
    x = _hold(embed[tokens].astype(jnp.float32) * s["embed_mult"], held)
    x = x.reshape(n // rows, rows, t, s["d"])
    for index in range(s["n_layers"]):
        x = layer(x, s, seed, index, precision)
    return x, embed


def _head(x, embed, s, op):
    """Logits of normed-to-be x [..., d]: ``norm(x) E^T / logits_scaling``."""
    z = _contract("...d,vd->...v", _rmsnorm(x, s["eps"]), embed, op, jnp.float32)
    return z / s["logit_scale"]


@functools.partial(jax.jit, static_argnames=("shape", "precision", "rows"))
def _score(tokens, want, seed, *, shape, precision, rows):
    s = dict(shape)
    op = PRECISIONS[precision][0]
    x, embed = _forward(tokens, seed, shape=shape, precision=precision, rows=rows)
    n, t = tokens.shape

    def read(args):
        xb, wb = args
        z = _head(xb, embed, s, op)
        return (z.max(-1), z.argmax(-1).astype(jnp.int32),
                jnp.take_along_axis(z, wb[..., None], -1)[..., 0])

    best, first, at_want = jax.lax.map(read, (x, want.reshape(n // rows, rows, t)))
    return best.reshape(n, t), first.reshape(n, t), at_want.reshape(n, t)


def logits(shape: dict, seed: int, tokens, precision: str = "float32"):
    """Full forward, logits [N, T, vocab] float32 (small sizes: the unit tests)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x, embed = _forward(tokens, jnp.asarray(seed, jnp.int32), shape=_frozen(shape),
                        precision=precision, rows=1)
    z = _head(x, embed, shape, PRECISIONS[precision][0])
    return z.reshape(tokens.shape + (shape["vocab"],))


def score(sizes: dict, seed: int, tokens, want, precision: str, rows: int = 1,
          block: int = 16):
    """One forward of the model the configuration states with the weights of
    ``seed``, in one of ``PRECISIONS``, over tokens [N, T] int32, ``block``
    requests at a time, ``rows`` of them through a layer's pieces at a time.
    For every position, whose logits predict the next token: the best logit,
    the token that has it, and the logit of ``want`` [N, T] there, each
    [N, T]. Every layer is causal, so trailing padding changes nothing before
    it."""
    shape = _frozen(shape_of(sizes))
    tokens = jnp.asarray(tokens, jnp.int32)
    want = jnp.asarray(want, jnp.int32)
    block = min(block, tokens.shape[0])
    outs = [_score(tokens[i:i + block], want[i:i + block], jnp.asarray(seed, jnp.int32),
                   shape=shape, precision=precision, rows=rows)
            for i in range(0, tokens.shape[0], block)]
    return tuple(jnp.concatenate(col, 0) for col in zip(*outs))
