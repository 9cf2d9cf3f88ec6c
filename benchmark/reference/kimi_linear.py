"""Plain reference of Kimi-Linear: Kimi Delta Attention (KDA) layers beside
latent (MLA, no positional encoding) layers, a leading dense FFN, then
sigmoid-routed experts with a shared expert, for one chip's share of the
routed experts.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision("highest")``:
no cache, no chunked recurrence, no absorbed attention, no sorting, no kernels.
Layer l of 1..n_layers, ``x`` the residual, every norm an RMSNorm (eps 1e-5)::

    x = x + Attn_l(norm(x));  x = x + FFN_l(norm(x))

``Attn_l`` is MLA where l is in ``full_attn_layers``, KDA elsewhere; ``FFN_l``
is a SwiGLU of width ``intermediate_size`` for the first ``first_k_dense_replace``
layers and the expert layer after them. Final norm, untied head.

KDA (H heads of d_k = d_v = head_dim; per head)::

    q~, k~, v = SiLU(conv4(a W_q)), SiLU(conv4(a W_k)), SiLU(conv4(a W_v))
    q = q~ / |q~| / sqrt(d_k);  k = k~ / |k~|
    g_t = -exp(A_log) softplus(W_fb (W_fa a_t) + dt_bias)   in R^{d_k},  alpha_t = exp(g_t)
    beta_t = sigmoid(a_t W_beta)                              a scalar per head
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t
    y_t = W_o [ RMSNorm_head(o_t) * sigmoid(W_gb (W_ga a_t)) ]

``conv4`` is a causal depthwise convolution over the last 4 positions, no bias;
the recurrence runs token by token in float32 in every precision.

MLA without rotation: ``q = a W_q`` (H x (nope + rope)); ``[c | k_r] = a W_kva``;
``c = norm(c)``; per head ``[k_nope | v] = c W_kvb``; score
``(q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope)``, causal softmax in float32,
``o = (sum p v) W_o``. The ``rope`` dims are kept and not rotated.

Expert layer: ``s = sigmoid(b W_r)`` over all ``n_routed`` outputs, float32 at
highest precision whatever the precision of the rest; the top ``topk`` of
``s + bias`` are chosen; their weights are ``s / sum(s chosen) * scale``;
``y = E_shared(b) + sum_{chosen, held here} w_e E_e(b)``. This chip holds experts
``[expert_offset, expert_offset + n_held)``; what the absent experts would have
added is left out. Experts are computed by a loop over the held experts, every
token through every one, masked by its gate.

It imports nothing of the program. The weights are what ``seed:<n>`` means: the
recipe of ``weight_key`` / ``matrix_shapes`` below (float32 draws, one key per tensor,
layer by layer, a routed expert's key from its index among ALL routed experts),
rounded once to the dtype a precision holds them in. ``score`` gets the sizes
``benchmark/lib/shapes.sizes_of`` knows; the rest is read from the configuration
file under ``benchmark/configs/`` that names this module and has those sizes.

Precisions (the configuration's ``reference`` block names one):

  float32           true float32 everywhere (the CPU rehearsal and unit tests)
  bf16_operands     what kimi-linear-48b-a3b states: weights, latent cache and
                    convolution inputs stored in bfloat16; residual, norms,
                    softmax, router, gates' activations and the recurrent state
                    in float32; every other contraction on bfloat16 operands
                    with float32 accumulation
  bf16_activations  the control: the residual stream and every activation held
                    in bfloat16 (the recurrent state stays float32)
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
K_WQ, K_WK, K_WV, K_WO, K_WFA, K_WFB, K_WGA, K_WGB, K_WBETA = range(1, 10)
K_CONV_Q, K_CONV_K, K_CONV_V, K_A_LOG, K_DT_BIAS = 10, 11, 12, 13, 14
M_WQ, M_WKVA, M_WKVB, M_WO = 20, 21, 22, 23
FFN_GATE, FFN_UP, FFN_DOWN = 30, 31, 32
ROUTER, ROUTER_BIAS, EXP_GATE, EXP_UP, EXP_DOWN = 40, 41, 50, 51, 52
SH_GATE, SH_UP, SH_DOWN = 60, 61, 62
EMBED, HEAD, LAYERS = 1, 2, 3
EMBED_STD = 0.02
ROUTER_BIAS_STD = 1e-2   # small against the spread of s (the 8th pick is ~0.9)
A_RANGE = (1.0, 16.0)    # A = exp(A_log) ~ U(1, 16)
DT_RANGE = (1e-3, 1e-1)  # softplus(dt_bias) ~ exp(U(log 1e-3, log 1e-1))
NORM_EPS = 1e-6          # inside the L2 norms of q~ and k~

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def shape_of(sizes: dict) -> dict:
    """The configuration file that names this module and has ``sizes``'s
    widths, depth and vocabulary, reduced to what the forward needs."""
    for path in sorted(glob.glob(os.path.join(_CONFIGS, "*.json"))):
        with open(path) as f:
            c = json.load(f)
        if c.get("reference", {}).get("module") != "kimi_linear":
            continue
        if (c["hidden_size"], c["num_attention_heads"], c["num_hidden_layers"],
                c["intermediate_size"], c["vocab_size"]) == (
                sizes["d_model"], sizes["n_heads"], sizes["n_layers"],
                sizes["d_ff"], sizes["vocab"]):
            return shape_from_config(c)
    raise SystemExit(f"no benchmark/configs/*.json names reference kimi_linear "
                     f"with the sizes {sizes}")


def shape_from_config(c: dict) -> dict:
    lin = c["linear_attn_config"]
    n_layers = c["num_hidden_layers"]
    return {
        "d": c["hidden_size"], "heads": c["num_attention_heads"],
        "kv_rank": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
        "rope": c["qk_rope_head_dim"], "v_dim": c["v_head_dim"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "gate_rank": c.get("assumed_sizes", {}).get("kda_gate_rank", lin["head_dim"]),
        "mla_layers": tuple(l for l in lin["full_attn_layers"] if l <= n_layers),
        "n_dense": c["first_k_dense_replace"],
        "d_ff": c["intermediate_size"], "d_expert": c["moe_intermediate_size"],
        "n_routed": c.get("reduced_from", {}).get("num_experts", c["num_experts"]),
        "topk": c["num_experts_per_token"],
        "scale": float(c["routed_scaling_factor"]),
        "eps": float(c["rms_norm_eps"]),
        "n_layers": n_layers, "vocab": c["vocab_size"],
        "n_held": c["num_experts"], "expert_offset": c.get("expert_offset", 0),
    }


def weight_key(seed, layer=None, tensor: int = 0, expert=None):
    """The key of one tensor of ``seed:<n>``. Embedding and head:
    ``fold_in(PRNGKey(seed), EMBED | HEAD)``. Tensor ``t`` of layer l (1-based):
    ``fold_in(fold_in(fold_in(PRNGKey(seed), LAYERS), l - 1), t)``, and a
    routed expert's folds its index among all routed experts in last."""
    key = jax.random.PRNGKey(seed)
    if layer is None:
        return jax.random.fold_in(key, tensor)
    key = jax.random.fold_in(jax.random.fold_in(key, LAYERS), layer - 1)
    key = jax.random.fold_in(key, tensor)
    return key if expert is None else jax.random.fold_in(key, expert)


def matrix_shapes(s: dict) -> dict:
    """tensor id -> (fan_in, fan_out) of every matrix drawn normal(0, 1/fan_in)."""
    d, h = s["d"], s["heads"]
    c = s["kda_heads"] * s["kda_dim"]
    r = s["gate_rank"]
    return {
        K_WQ: (d, c), K_WK: (d, c), K_WV: (d, c), K_WO: (c, d),
        K_WFA: (d, r), K_WFB: (r, c), K_WGA: (d, r), K_WGB: (r, c),
        K_WBETA: (d, s["kda_heads"]),
        K_CONV_Q: (s["conv"], c), K_CONV_K: (s["conv"], c), K_CONV_V: (s["conv"], c),
        M_WQ: (d, h * (s["nope"] + s["rope"])), M_WKVA: (d, s["kv_rank"] + s["rope"]),
        M_WKVB: (s["kv_rank"], h * (s["nope"] + s["v_dim"])),
        M_WO: (h * s["v_dim"], d),
        FFN_GATE: (d, s["d_ff"]), FFN_UP: (d, s["d_ff"]), FFN_DOWN: (s["d_ff"], d),
        ROUTER: (d, s["n_routed"]),
        EXP_GATE: (d, s["d_expert"]), EXP_UP: (d, s["d_expert"]),
        EXP_DOWN: (s["d_expert"], d),
        SH_GATE: (d, s["d_expert"]), SH_UP: (d, s["d_expert"]),
        SH_DOWN: (s["d_expert"], d),
    }


def _int8(w):
    """Symmetric int8 with one scale per output feature, dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _matrix(s, key, tensor, op, quant):
    """One matrix: normal(0, 1/fan_in) in float32, rounded once to ``op``."""
    shape = matrix_shapes(s)[tensor]
    w = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return (_int8(w) if quant else w).astype(op)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def gate_vectors(s, key_of):
    """(A_log [H], dt_bias [H * d_k]) of a KDA layer, float32 in every
    precision: ``A_log = log u``, ``u ~ U(1, 16)``; ``dt_bias`` the inverse
    softplus of ``exp(U(log 1e-3, log 1e-1))``."""
    a_log = jnp.log(_uniform(key_of(K_A_LOG), (s["kda_heads"],), *A_RANGE))
    dt = jnp.exp(_uniform(key_of(K_DT_BIAS), (s["kda_heads"] * s["kda_dim"],),
                          math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


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


def _l2norm(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + NORM_EPS)


def _conv4(u, w, op, held):
    """Causal depthwise convolution over the last ``taps`` positions: u
    [B, T, C], w [taps, C]; inputs and taps rounded to ``op`` (the inputs are
    what the served model keeps between steps), products summed in float32."""
    taps, t = w.shape[0], u.shape[1]
    up = jnp.pad(_rounded(u, op), ((0, 0), (taps - 1, 0), (0, 0)))
    w32 = _rounded(w, op)
    y = sum(up[:, i:i + t] * w32[i] for i in range(taps))
    return _hold(y, held)


KDA_MATRICES = (K_WQ, K_WK, K_WV, K_WO, K_WFA, K_WFB, K_WGA, K_WGB, K_WBETA,
                K_CONV_Q, K_CONV_K, K_CONV_V)
MLA_MATRICES = (M_WQ, M_WKVA, M_WKVB, M_WO)


def attention_weights(s, key_of, op, quant, latent: bool) -> dict:
    """tensor id -> tensor of one attention (drawn once a layer, outside the
    loop over blocks)."""
    w = {t: _matrix(s, key_of(t), t, op, quant)
         for t in (MLA_MATRICES if latent else KDA_MATRICES)}
    if not latent:
        w[K_A_LOG], w[K_DT_BIAS] = gate_vectors(s, key_of)
    return w


def kda(a, wts, s, op, held):
    """Kimi Delta Attention over a block ``a`` [B, T, d], token by token."""
    mm = functools.partial(_contract, op=op, held=held)
    w = wts.__getitem__
    b, t, _ = a.shape
    h, dk = s["kda_heads"], s["kda_dim"]
    a_log, dt_bias = wts[K_A_LOG], wts[K_DT_BIAS]

    def branch(wt, ct):
        y = _conv4(mm("btd,dc->btc", a, w(wt)), w(ct), op, held)
        return _hold(jax.nn.silu(y.astype(jnp.float32)), held).reshape(b, t, h, dk)

    q = _l2norm(branch(K_WQ, K_CONV_Q)) * dk ** -0.5
    k = _l2norm(branch(K_WK, K_CONV_K))
    v = branch(K_WV, K_CONV_V).astype(jnp.float32)
    f = mm("btr,rc->btc", mm("btd,dr->btr", a, w(K_WFA)), w(K_WFB))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias).reshape(b, t, h, dk)
    beta = jax.nn.sigmoid(mm("btd,dh->bth", a, w(K_WBETA)).astype(jnp.float32))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [B, H, dk] x4, [B, H]
        S = jnp.exp(g_t)[..., None] * S               # Diag(alpha) S
        u = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision="highest")
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - u)[:, :, None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision="highest")

    seq = tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dk), jnp.float32), seq)
    o = _hold(_rmsnorm(jnp.moveaxis(o, 0, 1), s["eps"]), held)     # [B, T, H, dv]
    gate = mm("btr,rc->btc", mm("btd,dr->btr", a, w(K_WGA)), w(K_WGB))
    o = _hold(o.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).reshape(b, t, h, dk), held)
    return mm("btc,cd->btd", o.reshape(b, t, h * dk), w(K_WO))


def mla(a, wts, s, op, held):
    """Latent attention without rotation over a block ``a`` [B, T, d]."""
    mm = functools.partial(_contract, op=op, held=held)
    w = wts.__getitem__
    b, t, _ = a.shape
    h, dn, dr, dv = s["heads"], s["nope"], s["rope"], s["v_dim"]
    pos = jnp.arange(t)
    q = mm("btd,de->bte", a, w(M_WQ)).reshape(b, t, h, dn + dr)
    ckr = mm("btd,de->bte", a, w(M_WKVA))
    c = _rmsnorm(ckr[..., :s["kv_rank"]], s["eps"])
    k_r = ckr[..., s["kv_rank"]:]
    kv = mm("btr,re->bte", c, w(M_WKVB)).reshape(b, t, h, dn + dv)
    sc = (_contract("bthd,bshd->bhts", q[..., :dn], kv[..., :dn], op, jnp.float32)
          + _contract("bthd,bsd->bhts", q[..., dn:], k_r, op, jnp.float32))
    sc = sc / math.sqrt(dn + dr)
    sc = jnp.where((pos[:, None] >= pos[None, :])[None, None], sc, -1e30)
    p = _hold(jax.nn.softmax(sc, axis=-1), held)
    o = mm("bhts,bshd->bthd", p, kv[..., dn:]).reshape(b, t, h * dv)
    return mm("bte,ed->btd", o, w(M_WO))


def ffn(b, w, op, held):
    mm = functools.partial(_contract, op=op, held=held)
    w_gate, w_up, w_down = w
    return mm("btf,fd->btd",
              jax.nn.silu(mm("btd,df->btf", b, w_gate)) * mm("btd,df->btf", b, w_up),
              w_down)


def router_weights(s, key_of):
    """(W_r, selection bias), float32 in every precision."""
    shape = matrix_shapes(s)[ROUTER]
    w_r = jax.random.normal(key_of(ROUTER), shape, jnp.float32) * shape[0] ** -0.5
    bias = jax.random.normal(key_of(ROUTER_BIAS), (s["n_routed"],),
                             jnp.float32) * ROUTER_BIAS_STD
    return w_r, bias


def route(b, w_r, bias, s):
    """-> gates [B, T, n_routed] float32: the renormalised, scaled weight at
    the chosen outputs, zero elsewhere. The router runs in float32 at highest
    precision in every precision."""
    sg = jax.nn.sigmoid(jnp.einsum("btd,dr->btr", b.astype(jnp.float32), w_r,
                                   precision="highest"))
    _, idx = jax.lax.top_k(sg + bias, s["topk"])
    chosen = jax.nn.one_hot(idx, s["n_routed"], dtype=jnp.float32).sum(-2)
    picked = chosen * sg
    return picked / picked.sum(-1, keepdims=True) * s["scale"]


def expert_layer(x, s, key_of, op, held, quant, n_held=None, expert_offset=None,
                 shared: bool = True):
    """The expert layer's share over normed blocks x [R, rows, T, d] -> its
    addition to the residual: the shared expert (where ``shared``) and the
    held experts' gated outputs."""
    n_held = s["n_held"] if n_held is None else n_held
    e0 = s["expert_offset"] if expert_offset is None else expert_offset

    w_shared = tuple(_matrix(s, key_of(t), t, op, quant)
                     for t in (SH_GATE, SH_UP, SH_DOWN)) if shared else None
    w_r, bias = router_weights(s, key_of)

    def start(xb):
        g = route(xb, w_r, bias, s)
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
    """Layer ``index`` (1-based, static: the layers differ in kind) over x
    [R, rows, T, d], a block of ``rows`` sequences at a time."""
    op, held, quant = PRECISIONS[precision]

    def key_of(tensor, expert=None):
        return weight_key(seed, index, tensor, expert)

    eps = s["eps"]
    latent = index in s["mla_layers"]
    attn = mla if latent else kda
    wts = attention_weights(s, key_of, op, quant, latent)
    x = jax.lax.map(lambda xb: xb + attn(_rmsnorm(xb, eps), wts, s, op, held), x)
    if index <= s["n_dense"]:
        w = tuple(_matrix(s, key_of(t), t, op, quant)
                  for t in (FFN_GATE, FFN_UP, FFN_DOWN))
        return jax.lax.map(lambda xb: xb + ffn(_rmsnorm(xb, eps), w, op, held), x)
    normed = jax.lax.map(lambda xb: _rmsnorm(xb, eps), x)
    return x + expert_layer(normed, s, key_of, op, held, quant)


def _frozen(s: dict):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("shape", "precision", "rows"))
def _forward(tokens, seed, *, shape, precision, rows):
    """tokens [N, T] -> hidden before the final norm [N/rows, rows, T, d] and
    the head."""
    s = dict(shape)
    op, held, quant = PRECISIONS[precision]
    embed = jax.random.normal(weight_key(seed, None, EMBED), (s["vocab"], s["d"]),
                              jnp.float32) * EMBED_STD
    if quant:
        embed = _int8(embed)
    n, t = tokens.shape
    x = embed.astype(op)[tokens].astype(held).reshape(n // rows, rows, t, s["d"])
    for index in range(1, s["n_layers"] + 1):
        x = layer(x, s, seed, index, precision)
    head = jax.random.normal(weight_key(seed, None, HEAD), (s["d"], s["vocab"]),
                             jnp.float32) * s["d"] ** -0.5
    return x, (_int8(head) if quant else head).astype(op)


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
