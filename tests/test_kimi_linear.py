"""Kimi-Linear block family (models/kimi_linear.py) against its plain reference
(benchmark/reference/kimi_linear.py) at a small size on the CPU, float32
storage, seeded: 5 layers (dense KDA, KDA, KDA, MLA, KDA), 8 experts of which
4 held. The chunkwise recurrence against the token recurrence, the full
forward, prefill then decode through the per-slot state and the paged latent
cache, chunked prefill carrying state from bucket to bucket, the shares of an
expert layer adding up to the uncut layer, what the batcher does with a slot
leaf (landing, no prefix adoption, preemption), the decode kernel against its
oracle, serving from a launch string, and the properties that refuse by name."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.kv import gather as kvg
from nnstreamer_tpu.models import kimi_linear as kl
from nnstreamer_tpu.models.serving import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(d_model=64, n_heads=4, kv_rank=16, nope=8, rope=8, v_dim=8,
             kda_heads=4, kda_dim=16, gate_rank=8, d_ff=128, d_expert=32,
             n_routed=8, topk=2, n_layers=5, vocab=97)
TOL = 3e-5  # float32 on the CPU: two sound orders of summation, logits of size ~4


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("ref_kimi_linear", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(n_held=4, offset=4, **over):
    return kl.KimiLinearConfig(**{**SIZES, **over}, n_held=n_held, expert_offset=offset)


def _shape(cfg):
    """The reference's own description of the same configuration."""
    return dict(d=cfg.d_model, heads=cfg.n_heads, kv_rank=cfg.kv_rank, nope=cfg.nope,
                rope=cfg.rope, v_dim=cfg.v_dim, kda_heads=cfg.kda_heads,
                kda_dim=cfg.kda_dim, conv=cfg.conv, gate_rank=cfg.gate_rank,
                mla_layers=cfg.mla_layers, n_dense=cfg.n_dense, d_ff=cfg.d_ff,
                d_expert=cfg.d_expert, n_routed=cfg.n_routed, topk=cfg.topk,
                scale=cfg.scale, eps=cfg.eps, n_layers=cfg.n_layers, vocab=cfg.vocab,
                n_held=cfg.n_held, expert_offset=cfg.expert_offset)


def _tokens(seed, shape, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(np.int32)


def _batcher(cfg, seed=11, **kw):
    base = dict(n_slots=2, max_len=128, prompt_len=32, kv_layout="paged",
                family=kl.KimiLinearFamily(cfg, jnp.float32))
    return ContinuousBatcher(kl.init_params(cfg, seed, jnp.float32), cfg.n_heads,
                             **{**base, **kw})


def _run(cb, rid, pump=4):
    while cb.result(rid) is None:
        cb.step_pump(pump)
    return np.asarray(cb.result(rid), np.int32)


def _assert_served_is_reference_best(ref, cfg, seed, prompt, served):
    full = np.concatenate([prompt, served])[None]
    z = np.asarray(ref.logits(_shape(cfg), seed, full, "float32"))[0]
    for j, tok in enumerate(served):
        row = z[len(prompt) - 1 + j]
        assert row.max() - row[tok] < TOL, j


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py")) as f:
        assert "nnstreamer_tpu" not in f.read().split('"""', 2)[2]


def test_layers_are_of_two_kinds_each_with_its_own_cache_index():
    cfg = _cfg()
    assert (cfg.mla_layers, cfg.n_mla, cfg.n_kda) == ((4,), 1, 4)
    layers = kl.init_params(cfg, 0, jnp.float32)["layers"]
    assert ["wqkv" in lp["attn"] for lp in layers] == [True, True, True, False, True]
    assert ["ffn" in lp for lp in layers] == [True, False, False, False, False]
    whole = kl.KimiLinearConfig()
    assert (whole.n_mla, whole.n_kda) == (7, 20)
    assert kl.config_from_options({"n_layers": "9"}).mla_layers == (4, 8)


# -- the recurrence ----------------------------------------------------------


def _gates(seed, b, t, h, dk, n_real=None):
    """Random recurrence inputs as ``kda_project`` makes them: unit k, strong
    and weak decays side by side, padding (beta = g = 0) after ``n_real``."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(b, t, h, dk)).astype(np.float32) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(5.0), (b, t, h, dk))).astype(np.float32)
    beta = rng.uniform(size=(b, t, h)).astype(np.float32)
    if n_real is not None:
        g[:, n_real:], beta[:, n_real:] = 0.0, 0.0
    state = rng.normal(size=(b, h, dk, dk)).astype(np.float32)
    return tuple(jnp.asarray(z) for z in (q, k, v, g, beta, state))


@pytest.mark.parametrize("t,chunk,n_real", [(100, 64, None), (64, 64, None),
                                            (96, 32, 70), (7, 64, 5)])
def test_chunkwise_recurrence_equals_the_token_recurrence(t, chunk, n_real):
    """A chunk boundary inside the prompt, a whole number of chunks and not,
    padding at the end: outputs at the real positions and the final state."""
    args = _gates(t, 2, t, 3, 8, n_real)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kl.kda_recurrent(*args)
        got_o, got_s = kl.kda_chunked(*args, chunk=chunk)
    n = n_real or t
    assert float(jnp.max(jnp.abs(got_o[:, :n] - want_o[:, :n]))) < 2e-5
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5


def test_padding_does_not_move_the_state_and_the_tail_is_the_last_real_inputs():
    cfg = _cfg()
    ap = kl.init_params(cfg, 2, jnp.float32)["layers"][1]["attn"]
    a = jnp.asarray(np.random.default_rng(0).normal(size=(1, 12, 64)), jnp.float32)
    live = jnp.arange(12)[None] < 7
    tail = jnp.zeros((1, 3, 3 * cfg.kda_width), jnp.float32)
    q, k, v, g, beta, window = kl.kda_project(a, live, tail, ap, cfg)
    assert float(jnp.max(jnp.abs(g[:, 7:]))) == 0.0 and float(jnp.max(beta[:, 7:])) == 0.0
    assert float(jnp.min(beta[:, :7])) > 0.0 and float(jnp.max(g[:, :7])) < 0.0
    np.testing.assert_array_equal(np.asarray(kl._real_tail(window, live, 4)),
                                  np.asarray(window[:, 7:10]))
    short = kl._real_tail(window, jnp.arange(12)[None] < 2, 4)   # reaches the old tail
    np.testing.assert_array_equal(np.asarray(short), np.asarray(window[:, 2:5]))
    _, s_pad = kl.kda_chunked(q, k, v, g, beta, jnp.zeros((1, 4, 16, 16)))
    _, s_real = kl.kda_chunked(q[:, :7], k[:, :7], v[:, :7], g[:, :7], beta[:, :7],
                               jnp.zeros((1, 4, 16, 16)))
    assert float(jnp.max(jnp.abs(s_pad - s_real))) < 1e-6


@pytest.mark.parametrize("case", ["dead-lanes-two-groups", "one-group"])
def test_decode_kernel_equals_its_oracle(case):
    from nnstreamer_tpu.ops.pallas import registry

    spec = registry.get("kda_decode_step")
    got, want, atol = spec.run_case(dict(next(
        c.params for c in spec.cases if c.name == case)))
    assert float(jnp.max(jnp.abs(got - want))) < atol


def test_decode_kernel_leaves_dead_lanes_and_other_layers_untouched():
    from nnstreamer_tpu.ops.pallas.kda import kda_decode_step

    q, k, v, g, beta, _ = _gates(3, 4, 1, 8, 16)
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.normal(size=(3, 5, 8, 16, 16)), jnp.float32)
    active = jnp.asarray([True, False, True, False])
    new, o = kda_decode_step(state, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                             beta[:, 0], active, layer=1, interpret=True)
    new, state = np.asarray(new), np.asarray(state)
    np.testing.assert_array_equal(new[[0, 2]], state[[0, 2]])          # other layers
    np.testing.assert_array_equal(new[1, [1, 3]], state[1, [1, 3]])    # dead lanes
    assert np.abs(new[1, [0, 2]] - state[1, [0, 2]]).max() > 1e-3      # live ones moved
    assert float(jnp.max(jnp.abs(o[jnp.asarray([1, 3])]))) == 0.0


# -- whole forwards ----------------------------------------------------------


@pytest.mark.parametrize("seed,n_held,offset,t", [(5, 8, 0, 100), (7, 1, 7, 70)])
def test_full_forward_matches_reference(ref, seed, n_held, offset, t):
    cfg = _cfg(n_held, offset)
    params = kl.init_params(cfg, seed, jnp.float32)
    toks = _tokens(seed, (2, t))
    got = kl.apply(params, jnp.asarray(toks), cfg)
    want = ref.logits(_shape(cfg), seed, toks, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_padding_changes_nothing_before_it(ref):
    cfg = _cfg()
    params = kl.init_params(cfg, 3, jnp.float32)
    toks = _tokens(3, (1, 20))
    padded = np.full((1, 32), -1, np.int32)
    padded[:, :20] = toks
    got = kl.apply(params, jnp.asarray(padded), cfg)[:, :20]
    want = ref.logits(_shape(cfg), 3, toks, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("n_buckets", [2, 3])
def test_chunk_over_buckets_equals_one_prefill(n_buckets):
    """State and convolution tails ride the stage from bucket to bucket; the
    last bucket is padded."""
    cfg = _cfg()
    params = kl.init_params(cfg, 4, jnp.float32)
    fam = kl.KimiLinearFamily(cfg, jnp.float32)
    p, t = 32, 32 * n_buckets - 9
    toks = _tokens(n_buckets, (1, t))
    want_logits, want_stage = kl.prefill(params, jnp.asarray(toks), cfg, jnp.float32)
    stage = fam.stage(32 * (n_buckets + 1))
    for i in range(n_buckets):
        bucket = np.full((1, p), -1, np.int32)
        part = toks[:, i * p:(i + 1) * p]
        bucket[:, :part.shape[1]] = part
        logits, stage, _ = fam.chunk(params, jnp.asarray(bucket),
                                     jnp.asarray(i * p, jnp.int32), stage)
    last = t - (n_buckets - 1) * p
    assert float(jnp.max(jnp.abs(logits[:, :last] - want_logits[:, -last:]))) < TOL
    assert float(jnp.max(jnp.abs(stage[0][:, :, :t] - want_stage[0]))) < TOL   # latents
    assert float(jnp.max(jnp.abs(stage[2] - want_stage[2]))) < TOL             # state
    np.testing.assert_allclose(np.asarray(stage[3]), np.asarray(want_stage[3]),
                               atol=TOL)                                       # tails


@pytest.mark.parametrize("seed,kernel", [(2, False), (9, True)])
def test_prefill_then_decode_through_state_and_latents_matches_reference(
        ref, seed, kernel):
    """Each prompt's stage is landed by the batcher's own staging op: latents
    into arena blocks through the tables, state and tails into the slot's row.
    Every later token is one ``decode_step`` (teacher-forced), with the
    kernels in interpret mode or the XLA formulation; its logits are the
    reference's full forward at that position. Slot 2 is dead and keeps what
    its row held."""
    from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_decode_attention

    cfg = _cfg()
    params = kl.init_params(cfg, seed, jnp.float32)
    fam = kl.KimiLinearFamily(cfg, jnp.float32)
    bs, nb, n_prompt, n_new = 16, 4, (19, 32, 7), 9
    full = _tokens(seed, (3, 32 + n_new))
    want = np.asarray(ref.logits(_shape(cfg), seed, full, "float32"))
    arena = fam.arena(3 * nb, bs, False, 3)
    arena = arena[:2] + (arena[2].at[:, 2].set(7.0), arena[3])
    tables = 1 + np.random.default_rng(seed).permutation(3 * nb).reshape(3, nb)
    _, land = kvg.make_staging_ops(False, jnp.float32)
    for b, n in enumerate(n_prompt):
        padded = np.full((1, 32), -1, np.int32)
        padded[0, :n] = full[b, :n]
        _, stage, _ = fam.prefill(params, jnp.asarray(padded))
        ids = np.zeros((2,), np.int32)
        ids[:-(-n // bs)] = tables[b, :-(-n // bs)]
        arena = land(arena, stage, jnp.asarray(ids), jnp.asarray(ids > 0), np.int32(b))
    active = jnp.asarray([True, True, False])
    tables = jnp.asarray(tables.astype(np.int32))
    attn_fn = (functools.partial(mla_paged_decode_attention, interpret=True)
               if kernel else None)
    step = jax.jit(lambda tok, pos, arena: kl.decode_step(
        params, tok, pos, active, arena, tables, cfg, attn_fn=attn_fn))
    pos = jnp.asarray(n_prompt, jnp.int32)
    dead_row = (np.asarray(arena[2][:, 2]), np.asarray(arena[3][:, 2]))
    for j in range(n_new):
        tok = jnp.asarray([full[b, n_prompt[b] + j] for b in range(3)], jnp.int32)
        logits, arena, pos2, aux = step(tok, pos, arena)
        for b in (0, 1):
            err = np.max(np.abs(np.asarray(logits[b]) - want[b, n_prompt[b] + j]))
            assert err < TOL, (b, j, err)
        assert np.array_equal(np.asarray(pos2 - pos), [1, 1, 0])
        aux = dict(zip(kl.AUX_NAMES, np.asarray(aux)))
        assert aux["tokens"] == 2 * 4 and aux["picks"] == 2 * 4 * cfg.topk
        assert aux["state_updates"] == 2 * cfg.n_kda
        pos = pos2
    np.testing.assert_array_equal(np.asarray(arena[2][:, 2]), dead_row[0])
    np.testing.assert_array_equal(np.asarray(arena[3][:, 2]), dead_row[1])
    assert float(jnp.max(jnp.abs(arena[0][:, 0]))) == 0.0  # scratch block stays pristine


# -- the expert layer --------------------------------------------------------


def test_router_is_a_renormalised_sigmoid_and_the_bias_moves_only_the_choice():
    cfg = _cfg()
    lp = dict(kl.init_params(cfg, 4, jnp.float32)["layers"][1])
    b = jnp.asarray(np.random.default_rng(1).normal(size=(6, cfg.d_model)), jnp.float32)
    idx0, w0 = kl.route(b, {**lp, "router_bias": jnp.zeros((8,))}, cfg)
    assert float(jnp.max(jnp.abs(jnp.sum(w0, -1) - cfg.scale))) < 1e-5
    never = int(np.argmin(np.bincount(np.asarray(idx0).ravel(), minlength=8)))
    bias = jnp.zeros((8,), jnp.float32).at[never].set(10.0)
    idx1, w1 = kl.route(b, {**lp, "router_bias": bias}, cfg)
    assert bool(jnp.all(jnp.any(idx1 == never, axis=-1)))   # now always chosen
    s = jax.nn.sigmoid(jnp.einsum("td,dr->tr", b, lp["router"], precision="highest"))
    picked = jnp.take_along_axis(s, idx1, axis=-1)          # unbiased scores
    want = picked / picked.sum(-1, keepdims=True) * cfg.scale
    assert float(jnp.max(jnp.abs(w1 - want))) < 1e-6


def test_dead_tokens_reach_no_routed_expert():
    cfg = _cfg(n_held=8, offset=0)
    lp = kl.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(2).normal(size=(6, cfg.d_model)), jnp.float32)
    live = jnp.asarray([True, False, True, False, False, True])
    y, stats = kl.moe(b, live, lp, cfg, shared=False)
    assert float(jnp.max(jnp.abs(y[~live]))) == 0.0
    assert [int(s) for s in stats] == [3, 3 * cfg.topk, int(stats[2]), 3 * cfg.topk]


def test_the_four_shares_add_up_to_the_uncut_expert_layer(ref):
    """Every share of the routed experts computes its own pairs; the shared
    expert, which every chip computes alike, is counted once: together they
    are the uncut reference's expert layer."""
    seed = 13
    b = jnp.asarray(np.random.default_rng(3).normal(size=(2, 12, 64)), jnp.float32)
    live = jnp.ones((24,), bool)
    shape = _shape(_cfg(8, 0))
    key_of = lambda t, e=None: ref.weight_key(seed, 3, t, e)  # noqa: E731
    whole = ref.expert_layer(b[None], shape, key_of, jnp.float32, jnp.float32, False)[0]
    summed, pairs = 0.0, 0
    for k in range(4):
        cfg = _cfg(2, 2 * k)
        lp = kl.init_params(cfg, seed, jnp.float32)["layers"][2]
        y, stats = kl.moe(b.reshape(-1, 64), live, lp, cfg, shared=(k == 0))
        summed, pairs = summed + y.reshape(b.shape), pairs + int(stats[1])
    assert pairs == 24 * cfg.topk        # every pick fell on exactly one share
    assert float(jnp.max(jnp.abs(summed - whole))) < 5e-5
    only_shared = ref.expert_layer(b[None], shape, key_of, jnp.float32, jnp.float32,
                                   False, n_held=0)[0]
    assert float(jnp.max(jnp.abs(whole - only_shared))) > 1e-2   # the experts add something


@pytest.mark.parametrize("path", ["dense", "grouped", "grouped-few-rows"])
def test_the_expert_layers_three_paths_agree(monkeypatch, path):
    """A step's few tokens run each held expert some token picked
    (``held_experts_ffn``); a bucket takes the sort-by-held-expert dispatch,
    above ``MOE_FEW_PAIRS`` pairs 5/16 of the rows first: the same layer, the
    same counters."""
    from nnstreamer_tpu.models import longcat as lc

    cfg = _cfg(2, 2)
    lp = kl.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(5).normal(size=(40, cfg.d_model)), jnp.float32)
    live = jnp.ones((40,), bool).at[7].set(False)
    idx, w = kl.route(b, lp, cfg)
    want = np.zeros((40, cfg.d_model), np.float32)      # pair by pair
    for t in range(40):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if live[t] and 2 <= e < 4:
                sp = {"w_gate": lp["e_gate"][e - 2], "w_up": lp["e_up"][e - 2],
                      "w_down": lp["e_down"][e - 2]}
                want[t] += we * np.asarray(lc.ffn(b[None, t:t + 1], sp))[0, 0]
    monkeypatch.setattr(kl, "MOE_DENSE_TOKENS", 128 if path == "dense" else 0)
    if path == "grouped-few-rows":
        monkeypatch.setattr(lc, "MOE_FEW_PAIRS", 16)
    got, stats = kl.moe(b, live, lp, cfg, shared=False)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    local = np.asarray(live)[:, None] & (np.asarray(idx) >= 2) & (np.asarray(idx) < 4)
    assert [int(s) for s in stats] == [
        39, int(local.sum()), len(set(np.asarray(idx)[local])), 39 * cfg.topk]


# -- what the batcher does with a slot leaf ----------------------------------


def test_a_repeated_prompt_is_not_adopted_as_a_prefix(ref):
    """The state at a block boundary is stored nowhere: the second request of
    the same prompt prefills from position 0 and serves the same tokens."""
    cfg = _cfg()
    cb = _batcher(cfg)
    prompt = _tokens(1, (45,))
    first = _run(cb, cb.submit(prompt, 8))
    again = _run(cb, cb.submit(prompt, 8))
    st = cb.stats()
    assert st["kv_prefix_hit_tokens"] == 0 and st["kv_prefix_hits"] == 0
    assert cb.probe_prefix(prompt) == 0
    np.testing.assert_array_equal(first, again)
    _assert_served_is_reference_best(ref, cfg, 11, prompt, first)
    with pytest.raises(ValueError, match="prefix sharing"):
        cb.register_prefix(prompt[:32])


def test_preempt_and_resume_gives_the_same_tokens(ref):
    """A pool too small for both streams: the younger request is preempted
    (blocks and state dropped), re-prefills prompt + served tokens through the
    chunk programs, and goes on from the state that gives."""
    cfg = _cfg()
    prompts = [_tokens(5, (30,)), _tokens(6, (28,))]
    roomy = _batcher(cfg)
    want = [_run(roomy, roomy.submit(p, 40)) for p in prompts]
    tight = _batcher(cfg, kv_blocks=8)   # 128 tokens of blocks for 2 x 70
    rids = [tight.submit(p, 40) for p in prompts]
    got = [_run(tight, r) for r in rids]
    assert tight.stats()["kv_preemptions"] >= 1
    for g, w, p in zip(got, want, prompts):
        np.testing.assert_array_equal(g, w)
        _assert_served_is_reference_best(ref, cfg, 11, p, g)


def test_launch_span_and_gauge_carry_the_slot_state_bytes():
    from nnstreamer_tpu.obs import metrics as obs_metrics

    cfg = _cfg()
    reg = obs_metrics.enable()
    try:
        cb = _batcher(cfg)
        per_slot = cfg.n_kda * (4 * 16 * 16 * 4 + 3 * 3 * cfg.kda_width * 4)
        assert cb._slot_state_bytes == per_slot
        assert reg.find("nns_slot_state_bytes").value == 2 * per_slot
        _run(cb, cb.submit(_tokens(0, (9,)), 6))
        assert reg.find("nns_slot_state_updates_total").value == cb.stats()[
            "moe_state_updates"] > 0
    finally:
        obs_metrics.disable()


# -- served from a launch string ---------------------------------------------

LAUNCH = ("d_model:64,n_heads:4,kv_rank:16,nope:8,rope:8,v_dim:8,kda_heads:4,kda_dim:16,"
          "gate_rank:8,d_ff:128,d_expert:32,n_routed:8,topk:2,n_layers:5,experts_held:4,"
          "expert_offset:4,vocab:97,dtype:float32,seed:11")


def _serve(prompts, new_tokens, **props):
    from nnstreamer_tpu.elements.llm_serve import LlmServerSink, LlmServerSrc
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.tensors.frame import Frame
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    src = AppSrc(name="in", spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
    out = TensorSink(name="out", **{"max-stored": 64})
    base = {"model": "zoo:kimi_linear_lm", "custom": LAUNCH, "id": "kl", "n-slots": 2,
            "max-len": 128, "prompt-len": 32, "kv-layout": "paged", "pump": 4}
    pipe = Pipeline().chain(src, LlmServerSink(name="llm", **{**base, **props}))
    pipe.chain(LlmServerSrc(name="llmsrc", id="kl"), out)
    got = {}
    out.connect("new-data", lambda f: got.__setitem__(
        f.meta["i"], np.asarray(f.tensors[0]).reshape(-1)))
    ex = pipe.start()
    try:
        for i, p in enumerate(prompts):
            src.push(Frame((p[None, :],), meta={"max_new_tokens": new_tokens, "i": i}))
        src.end_of_stream()
        ex.wait(120.0)
        if ex.errors:
            raise ex.errors[0]
        stats = pipe["llmsrc"].serving_stats()
    finally:
        ex.stop()
    return got, stats


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_served_from_a_launch_string_through_the_paged_batcher(ref, attn_impl):
    """``appsrc ! tensor_llm_serversink model=zoo:kimi_linear_lm``: prompts
    under and over the bucket (chunked prefill), three requests on two slots
    (a slot's row is landed over what the last request left); every served
    token is the reference's best at its position."""
    prompts = [_tokens(i, (n,)) for i, n in enumerate((9, 50, 32))]
    got, stats = _serve(prompts, 10, **{"attn-impl": attn_impl})
    assert stats["family"] == "kimi_linear" and stats["attn_impl"] == attn_impl
    assert stats["moe_picks"] == stats["moe_tokens"] * 2 > 0
    assert stats["moe_state_updates"] * 4 == stats["moe_tokens"] * 4  # 4 KDA, 4 expert layers
    for i, p in enumerate(prompts):
        assert len(got[i]) == 10
        _assert_served_is_reference_best(ref, _cfg(), 11, p, got[i])


@pytest.mark.parametrize("props,named", [
    ({"speculate": "4"}, "speculate"),
    ({"cache-dtype": "int8"}, "cache-dtype=int8"),
    ({"kv-layout": "slot"}, "kv-layout=slot"),
    ({"role": "decode"}, "role"),
    ({"checkpoint-every-tokens": "4", "checkpoint-dir": "/tmp/nns-kl-ckpt"},
     "checkpoint-every-tokens"),
])
def test_properties_the_family_does_not_carry_refuse_by_name(props, named):
    with pytest.raises(Exception) as err:
        _serve([_tokens(0, (5,))], 2, **props)
    assert named in str(err.value), str(err.value)


@pytest.mark.parametrize("call,named", [
    (lambda cb: cb.spec_step(k=2), "speculate"),
    (lambda cb: cb.extract_request(0), "migration"),
    (lambda cb: cb.snapshot(), "snapshot"),
    (lambda cb: cb.register_prefix(np.arange(8)), "prefix sharing"),
])
def test_batcher_refuses_what_the_family_does_not_carry(call, named):
    cb = _batcher(_cfg(), max_len=64)
    with pytest.raises(ValueError, match=named):
        call(cb)


@pytest.mark.parametrize("kw,named", [
    ({"windowed": True}, "windowed"),
    ({"mesh": object()}, "mesh"),
    ({"draft_params": {}}, "draft model"),
    ({"kv_layout": "slot"}, "kv-layout=slot"),
    ({"cache_dtype": "int8"}, "cache-dtype=int8"),
])
def test_construction_refuses_what_the_family_does_not_carry(kw, named):
    with pytest.raises(ValueError, match=named):
        ContinuousBatcher({}, 4, **{"kv_layout": "paged", **kw},
                          family=kl.KimiLinearFamily(_cfg(), jnp.float32))


def test_state_instant_is_on_the_profilers_timeline(tmp_path):
    """``nns.state.update`` beside ``nns.moe.routing``: one instant each per
    harvested pump; ``nns.pump.launch`` carries the live lanes' state bytes."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, stats = _serve([_tokens(0, (9,)), _tokens(1, (20,))], 6)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events = [(ev.name, dict(ev.stats)) for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events if ev.name.startswith("nns.")]
    updates = [s for name, s in events if name == "nns.state.update"]
    routing = [s for name, s in events if name == "nns.moe.routing"]
    assert len(updates) == len(routing) > 0
    assert sum(u["slot_layers"] for u in updates) == stats["moe_state_updates"]
    cfg = _cfg()
    assert all(u["bytes"] == u["slot_layers"] * 2 * 4 * 16 * 16 * 4 for u in updates)
    launches = [s for name, s in events if name == "nns.pump.launch"]
    per_slot = cfg.n_kda * (4 * 16 * 16 * 4 + 3 * 3 * cfg.kda_width * 4)
    assert launches and all(s["state_bytes"] == s["active"] * per_slot for s in launches)
