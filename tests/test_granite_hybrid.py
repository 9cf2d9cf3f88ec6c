"""Granite-4.0-H block family (models/granite_hybrid.py) against its plain
reference (benchmark/reference/granite_hybrid.py) at a small size on the CPU,
float32 storage, seeded: 4 layers (mamba, mamba, attention, mamba), 8 experts
of which 4 held, top 3. The chunked scan against the token recurrence, the
full forward, prefill then decode through the per-slot state and the paged
K/V blocks, chunked prefill carrying state from bucket to bucket, the shares
of an expert layer adding up to the uncut layer, what the batcher does with a
slot leaf beside dense K/V blocks (landing, no prefix adoption, preemption),
the decode kernel against its oracle, serving from a launch string, the
properties that refuse by name, and the accepted expert families' programs
left as the parent lowered them."""

import functools
import hashlib
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.kv import gather as kvg
from nnstreamer_tpu.models import granite_hybrid as gh
from nnstreamer_tpu.models.serving import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8,
             ssm_head_dim=8, ssm_state=16, ssm_chunk=8, attn_layers=(2,),
             d_expert=32, d_shared=48, n_routed=8, topk=3, attn_scale=0.0625,
             n_layers=4, vocab=97)
# float32 on the CPU, two sound orders of summation (the chunked scan against
# the token recurrence, a sorted dispatch against a loop over experts). The
# logits are norm(x) E^T / 16 with an embedding of std 1e-3: about 5e-4 in
# size, so 5e-9 is the same 1e-5 of the value that the other families' 3e-5 on
# logits of size 4 is
TOL = 5e-9


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "granite_hybrid.py")
    spec = importlib.util.spec_from_file_location("ref_granite_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(n_held=4, offset=4, **over):
    return gh.GraniteHybridConfig(**{**SIZES, **over}, n_held=n_held,
                                  expert_offset=offset)


def _shape(cfg):
    """The reference's own description of the same configuration."""
    return dict(d=cfg.d_model, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, ssm_heads=cfg.ssm_heads,
                ssm_head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
                conv=cfg.conv, attn_layers=cfg.attn_here, d_expert=cfg.d_expert,
                d_shared=cfg.d_shared, n_routed=cfg.n_routed, topk=cfg.topk,
                eps=cfg.eps, embed_mult=cfg.embed_mult, resid_mult=cfg.resid_mult,
                attn_scale=cfg.attn_scale, logit_scale=cfg.logit_scale,
                n_layers=cfg.n_layers, vocab=cfg.vocab, n_held=cfg.n_held,
                expert_offset=cfg.expert_offset)


def _tokens(seed, shape, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(np.int32)


def _batcher(cfg, seed=11, **kw):
    base = dict(n_slots=2, max_len=128, prompt_len=32, kv_layout="paged",
                family=gh.GraniteHybridFamily(cfg, jnp.float32))
    return ContinuousBatcher(gh.init_params(cfg, seed, jnp.float32), cfg.n_heads,
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
    with open(os.path.join(ROOT, "benchmark", "reference", "granite_hybrid.py")) as f:
        assert "nnstreamer_tpu" not in f.read().split('"""', 2)[2]


def test_layers_are_of_two_kinds_each_with_its_own_cache_index():
    cfg = _cfg()
    assert (cfg.attn_here, cfg.n_attn, cfg.n_ssm) == ((2,), 1, 3)
    params = gh.init_params(cfg, 0, jnp.float32)
    assert ["w_in" in lp["mix"] for lp in params["layers"]] == [True, True, False, True]
    assert all("router" in lp and "shared" in lp for lp in params["layers"])
    assert "head" not in params                       # the head is the embedding
    whole = gh.GraniteHybridConfig()
    assert (whole.n_attn, whole.n_ssm, whole.d_inner, whole.conv_width) == (
        4, 36, 8192, 8448)
    cut = gh.config_from_options({"n_layers": "10", "experts_held": "36"})
    assert (cut.attn_here, cut.n_ssm, cut.n_held) == ((5,), 9, 36)
    assert gh.config_from_options({"attn_layers": "1/3", "n_layers": "3"}).attn_here == (1,)


# -- the scan ----------------------------------------------------------------


def _scan_inputs(seed, b, t, h, p, n, n_real=None):
    """Random scan inputs as ``ssm_project`` makes them: strong and weak
    decays side by side, padding (dt = log a = 0) after ``n_real``."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t, h, p)).astype(np.float32)
    bm, cm = (rng.normal(size=(b, t, n)).astype(np.float32) for _ in range(2))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, t, h))).astype(np.float32)
    la = (-rng.uniform(1.0, 16.0, (h,)) * dt).astype(np.float32)
    if n_real is not None:
        dt[:, n_real:], la[:, n_real:] = 0.0, 0.0
    state = rng.normal(size=(b, h, p, n)).astype(np.float32)
    return tuple(jnp.asarray(z) for z in (x, bm, cm, dt, la, state))


@pytest.mark.parametrize("t,chunk,n_real", [(100, 64, None), (64, 64, None),
                                            (96, 32, 70), (7, 64, 5)])
def test_chunked_scan_equals_the_token_recurrence(t, chunk, n_real):
    """A chunk boundary inside the prompt, a whole number of chunks and not,
    padding at the end: outputs at the real positions and the final state."""
    args = _scan_inputs(t, 2, t, 3, 8, 16, n_real)
    want_y, want_s = gh.ssm_recurrent(*args)
    got_y, got_s = gh.ssm_chunked(*args, chunk=chunk)
    n = n_real or t
    assert float(jnp.max(jnp.abs(got_y[:, :n] - want_y[:, :n]))) < 3e-5
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 3e-5


def test_padding_does_not_move_the_state_and_the_tail_is_the_last_real_inputs():
    cfg = _cfg()
    sp = gh.init_params(cfg, 2, jnp.float32)["layers"][1]["mix"]
    a = jnp.asarray(np.random.default_rng(0).normal(size=(1, 12, 64)), jnp.float32)
    live = jnp.arange(12)[None] < 7
    tail = jnp.zeros((1, 3, cfg.conv_width), jnp.float32)
    z, x, bm, cm, dt, la, window = gh.ssm_project(a, live, tail, sp, cfg)
    assert float(jnp.max(jnp.abs(dt[:, 7:]))) == 0.0 and float(jnp.max(jnp.abs(la[:, 7:]))) == 0.0
    assert float(jnp.min(dt[:, :7])) > 0.0 and float(jnp.max(la[:, :7])) < 0.0
    np.testing.assert_array_equal(np.asarray(gh._real_tail(window, live, 4)),
                                  np.asarray(window[:, 7:10]))
    zero = jnp.zeros((1, 8, 8, 16))
    _, s_pad = gh.ssm_chunked(x, bm, cm, dt, la, zero, 8)
    _, s_real = gh.ssm_chunked(x[:, :7], bm[:, :7], cm[:, :7], dt[:, :7], la[:, :7],
                               zero, 8)
    assert float(jnp.max(jnp.abs(s_pad - s_real))) < 1e-6


@pytest.mark.parametrize("case", ["dead-lanes-two-groups", "one-group",
                                  "one-head-a-row"])
def test_decode_kernel_equals_its_oracle(case):
    from nnstreamer_tpu.ops.pallas import registry

    spec = registry.get("ssm_decode_step")
    got, want, atol = spec.run_case(dict(next(
        c.params for c in spec.cases if c.name == case)))
    assert float(jnp.max(jnp.abs(got - want))) < atol


# (H, P) -> heads side by side on a row of the leaf: the published widths,
# a head as wide as a vreg and wider, the tiny test widths, a head count that
# the vreg's width over P does not divide
PACKING = [(128, 64, 2), (32, 128, 1), (4, 256, 1), (8, 8, 8), (64, 8, 16),
           (6, 32, 3), (7, 16, 7), (5, 64, 1)]


@pytest.mark.parametrize("h,p,k", PACKING)
def test_heads_on_a_row_come_from_the_shapes(h, p, k):
    from nnstreamer_tpu.ops.pallas.ssm import heads_per_row

    assert heads_per_row(h, p) == k
    cfg = _cfg(ssm_heads=h, ssm_head_dim=p)
    state, tails = gh.empty_slot_stage(cfg, 3, jnp.float32)
    assert state.shape == (3, 3, h // k, 16, k * p) and state.dtype == jnp.float32
    assert state.size == 3 * 3 * h * p * 16               # the same bytes
    assert tails.shape == (3, 3, 3, h * p + 32)


@pytest.mark.parametrize("h,p,k", PACKING)
def test_leaf_layout_round_trip_is_exact(h, p, k):
    """[H, P, N] -> the leaf -> [H, P, N] moves values and changes none; in
    the leaf, head ``g k + j``'s channel ``c`` at state ``n`` is row ``g``,
    sublane ``n``, lane ``j P + c``."""
    from nnstreamer_tpu.ops.pallas.ssm import leaf_to_state, state_to_leaf

    s = np.random.default_rng(h * p).normal(size=(2, 3, h, p, 16)).astype(np.float32)
    leaf = np.asarray(state_to_leaf(jnp.asarray(s), k))
    assert leaf.shape == (2, 3, h // k, 16, k * p)
    np.testing.assert_array_equal(np.asarray(leaf_to_state(jnp.asarray(leaf), k)), s)
    head, c, n = h - 1, p // 2, 5
    np.testing.assert_array_equal(leaf[1, 2, head // k, n, (head % k) * p + c],
                                  s[1, 2, head, c, n])


@pytest.mark.parametrize("h,p", [(8, 8), (4, 64), (2, 128)])
def test_decode_oracle_over_the_leaf_is_the_token_recurrence(h, p):
    """``ssm_decode_step_ref`` on the leaf against ``ssm_recurrent`` on [H, P,
    N]: the layout moves values, the recurrence is the same."""
    from nnstreamer_tpu.ops.pallas.ssm import (
        heads_per_row, leaf_to_state, ssm_decode_step_ref, state_to_leaf)

    x, bm, cm, dt, la, s0 = _scan_inputs(h + p, 3, 1, h, p, 16)
    k = heads_per_row(h, p)
    want_y, want_s = gh.ssm_recurrent(x, bm, cm, dt, la, s0)
    leaf = state_to_leaf(jnp.stack([jnp.zeros_like(s0), s0]), k)
    new, y = ssm_decode_step_ref(leaf, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                                 jnp.exp(la[:, 0]), jnp.zeros((h,)),
                                 jnp.ones((3,), bool), layer=1)
    assert float(jnp.max(jnp.abs(y - want_y[:, 0]))) < 1e-5
    assert float(jnp.max(jnp.abs(leaf_to_state(new[1], k) - want_s))) < 1e-6
    assert float(jnp.max(jnp.abs(new[0]))) == 0.0


def test_decode_kernel_leaves_dead_lanes_and_other_layers_untouched():
    from nnstreamer_tpu.ops.pallas.ssm import ssm_decode_step

    x, bm, cm, dt, la, _ = _scan_inputs(3, 4, 1, 8, 8, 16)
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.normal(size=(3, 5, 1, 16, 64)), jnp.float32)
    active = jnp.asarray([True, False, True, False])
    new, y = ssm_decode_step(state, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                             jnp.exp(la[:, 0]), jnp.ones((8,)), active, layer=1,
                             interpret=True)
    new, state = np.asarray(new), np.asarray(state)
    np.testing.assert_array_equal(new[[0, 2]], state[[0, 2]])          # other layers
    np.testing.assert_array_equal(new[1, [1, 3]], state[1, [1, 3]])    # dead lanes
    assert np.abs(new[1, [0, 2]] - state[1, [0, 2]]).max() > 1e-3      # live ones moved
    assert float(jnp.max(jnp.abs(y[jnp.asarray([1, 3])]))) == 0.0


# -- whole forwards ----------------------------------------------------------


@pytest.mark.parametrize("seed,n_held,offset,t", [(5, 8, 0, 100), (7, 1, 7, 70)])
def test_full_forward_matches_reference(ref, seed, n_held, offset, t):
    cfg = _cfg(n_held, offset)
    params = gh.init_params(cfg, seed, jnp.float32)
    toks = _tokens(seed, (2, t))
    got = gh.apply(params, jnp.asarray(toks), cfg)
    want = ref.logits(_shape(cfg), seed, toks, "float32")
    assert float(jnp.max(jnp.abs(want))) > 5e-4          # logits of size ~5e-4
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_padding_changes_nothing_before_it(ref):
    cfg = _cfg()
    params = gh.init_params(cfg, 3, jnp.float32)
    toks = _tokens(3, (1, 20))
    padded = np.full((1, 32), -1, np.int32)
    padded[:, :20] = toks
    got = gh.apply(params, jnp.asarray(padded), cfg)[:, :20]
    want = ref.logits(_shape(cfg), 3, toks, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("n_buckets", [2, 3])
def test_chunk_over_buckets_equals_one_prefill(n_buckets):
    """State and convolution tails ride the stage from bucket to bucket, keys
    and values are written at the bucket's position; the last bucket is padded."""
    cfg = _cfg()
    params = gh.init_params(cfg, 4, jnp.float32)
    fam = gh.GraniteHybridFamily(cfg, jnp.float32)
    p, t = 32, 32 * n_buckets - 9
    toks = _tokens(n_buckets, (1, t))
    want_logits, want_stage = gh.prefill(params, jnp.asarray(toks), cfg, jnp.float32)
    stage = fam.stage(32 * (n_buckets + 1))
    for i in range(n_buckets):
        bucket = np.full((1, p), -1, np.int32)
        part = toks[:, i * p:(i + 1) * p]
        bucket[:, :part.shape[1]] = part
        logits, stage, _ = fam.chunk(params, jnp.asarray(bucket),
                                     jnp.asarray(i * p, jnp.int32), stage)
    last = t - (n_buckets - 1) * p
    assert float(jnp.max(jnp.abs(logits[:, :last] - want_logits[:, -last:]))) < TOL
    for leaf in (0, 1):                                                    # k, v
        assert float(jnp.max(jnp.abs(stage[leaf][:, :, :t] - want_stage[leaf]))) < 3e-5
    assert float(jnp.max(jnp.abs(stage[2] - want_stage[2]))) < 3e-5        # state
    np.testing.assert_allclose(np.asarray(stage[3]), np.asarray(want_stage[3]),
                               atol=3e-5)                                  # tails


@pytest.mark.parametrize("seed,kernel", [(2, False), (9, True)])
def test_prefill_then_decode_through_state_and_kv_blocks_matches_reference(
        ref, seed, kernel):
    """Each prompt's stage is landed by the batcher's own staging op: keys and
    values into arena blocks through the tables, state and tails into the
    slot's row. Every later token is one ``decode_step`` (teacher-forced),
    with the kernels in interpret mode or the XLA formulation; its logits are
    the reference's full forward at that position. Slot 2 is dead and keeps
    what its row held."""
    cfg = _cfg()
    params = gh.init_params(cfg, seed, jnp.float32)
    fam = gh.GraniteHybridFamily(cfg, jnp.float32)
    bs, nb, n_prompt, n_new = 16, 4, (19, 32, 7), 9
    full = _tokens(seed, (3, 32 + n_new))
    want = np.asarray(ref.logits(_shape(cfg), seed, full, "float32"))
    arena = fam.arena(3 * nb, bs, False, 3)
    assert [a.shape for a in arena] == [
        (1, 13, 16, 2, 16), (1, 13, 16, 2, 16), (3, 4, 1, 16, 64), (3, 4, 3, 96)]
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
    attn_fn = fam.make_attention() if kernel else None    # interpreted off a TPU
    step = jax.jit(lambda tok, pos, arena: gh.decode_step(
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
        aux = dict(zip(gh.AUX_NAMES, np.asarray(aux)))
        assert aux["tokens"] == 2 * 4 and aux["picks"] == 2 * 4 * cfg.topk
        assert aux["state_updates"] == 2 * cfg.n_ssm
        pos = pos2
    np.testing.assert_array_equal(np.asarray(arena[2][:, 2]), dead_row[0])
    np.testing.assert_array_equal(np.asarray(arena[3][:, 2]), dead_row[1])
    assert float(jnp.max(jnp.abs(arena[0][:, 0]))) == 0.0  # scratch block stays pristine


# -- the expert layer --------------------------------------------------------


def test_router_weights_are_a_softmax_over_the_picks_alone():
    cfg = _cfg()
    lp = gh.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(1).normal(size=(6, cfg.d_model)), jnp.float32)
    idx, w = gh.route(b, lp, cfg)
    assert idx.shape == w.shape == (6, cfg.topk)
    assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 1.0))) < 1e-6
    logits = jnp.einsum("td,dr->tr", b, lp["router"], precision="highest")
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.argsort(-np.asarray(logits), -1)[:, :3], -1))
    picked = jnp.take_along_axis(logits, idx, axis=-1)
    assert float(jnp.max(jnp.abs(w - jax.nn.softmax(picked, -1)))) < 1e-6
    # not the softmax over all outputs renormalised late: the same, in exact
    # arithmetic, but never a weight of an output that was not picked
    assert float(jnp.min(w)) > 0.0


def test_dead_tokens_reach_no_routed_expert():
    cfg = _cfg(n_held=8, offset=0)
    lp = gh.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(2).normal(size=(6, cfg.d_model)), jnp.float32)
    live = jnp.asarray([True, False, True, False, False, True])
    y, stats = gh.moe(b, live, lp, cfg, shared=False)
    assert float(jnp.max(jnp.abs(y[~live]))) == 0.0
    assert [int(s) for s in stats] == [3, 3 * cfg.topk, int(stats[2]), 3 * cfg.topk]


def test_the_two_shares_add_up_to_the_uncut_expert_layer(ref):
    """Each half of the routed experts computes its own pairs; the shared MLP,
    which both chips compute alike, is counted once: together they are the
    uncut reference's expert layer."""
    seed = 13
    b = jnp.asarray(np.random.default_rng(3).normal(size=(2, 12, 64)), jnp.float32)
    live = jnp.ones((24,), bool)
    shape = _shape(_cfg(8, 0))
    key_of = lambda t, e=None: ref.weight_key(seed, 3, t, e)  # noqa: E731
    whole = ref.expert_layer(b[None], shape, key_of, jnp.float32, jnp.float32, False)[0]
    summed, pairs = 0.0, 0
    for k in range(2):
        cfg = _cfg(4, 4 * k)
        lp = gh.init_params(cfg, seed, jnp.float32)["layers"][3]
        y, stats = gh.moe(b.reshape(-1, 64), live, lp, cfg, shared=(k == 0))
        summed, pairs = summed + y.reshape(b.shape), pairs + int(stats[1])
    assert pairs == 24 * cfg.topk        # every pick fell on exactly one share
    assert float(jnp.max(jnp.abs(summed - whole))) < 5e-5
    only_shared = ref.expert_layer(b[None], shape, key_of, jnp.float32, jnp.float32,
                                   False, n_held=0)[0]
    assert float(jnp.max(jnp.abs(whole - only_shared))) > 1e-2   # the experts add something


@pytest.mark.parametrize("path", ["dense", "grouped", "grouped-few-rows"])
def test_the_expert_layers_three_paths_agree(monkeypatch, path):
    """A step's few tokens run every held expert densely; a bucket takes the
    sort-by-held-expert dispatch, above ``MOE_FEW_PAIRS`` pairs 9/16 of the
    rows first: the same layer, the same counters."""
    from nnstreamer_tpu.models import longcat as lc

    cfg = _cfg(4, 2)
    lp = gh.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(5).normal(size=(40, cfg.d_model)), jnp.float32)
    live = jnp.ones((40,), bool).at[7].set(False)
    idx, w = gh.route(b, lp, cfg)
    want = np.zeros((40, cfg.d_model), np.float32)      # pair by pair
    for t in range(40):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if live[t] and 2 <= e < 6:
                sp = {"w_gate": lp["e_gate"][e - 2], "w_up": lp["e_up"][e - 2],
                      "w_down": lp["e_down"][e - 2]}
                want[t] += we * np.asarray(lc.ffn(b[None, t:t + 1], sp))[0, 0]
    monkeypatch.setattr(gh, "MOE_DENSE_TOKENS", 128 if path == "dense" else 0)
    if path == "grouped-few-rows":
        monkeypatch.setattr(lc, "MOE_FEW_PAIRS", 16)
    got, stats = gh.moe(b, live, lp, cfg, shared=False)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    local = np.asarray(live)[:, None] & (np.asarray(idx) >= 2) & (np.asarray(idx) < 6)
    assert [int(s) for s in stats] == [
        39, int(local.sum()), len(set(np.asarray(idx)[local])), 39 * cfg.topk]


# -- what the batcher does with a slot leaf beside dense K/V blocks ----------


def test_a_repeated_prompt_is_not_adopted_as_a_prefix(ref):
    """The state at a block boundary is stored nowhere: the second request of
    the same prompt prefills from position 0 and serves the same tokens, though
    its K/V blocks are of the kind the dense family shares."""
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
        per_slot = cfg.n_ssm * (8 * 8 * 16 * 4 + 3 * cfg.conv_width * 4)
        assert cb._slot_state_bytes == per_slot
        assert reg.find("nns_slot_state_bytes").value == 2 * per_slot
        _run(cb, cb.submit(_tokens(0, (9,)), 6))
        assert reg.find("nns_slot_state_updates_total").value == cb.stats()[
            "moe_state_updates"] > 0
    finally:
        obs_metrics.disable()


def test_state_byte_counts_are_the_parents_at_the_published_widths(monkeypatch):
    """The leaf's layout moves no byte count: what ``nns.state.update``'s
    ``bytes``, ``nns.pump.launch``'s ``state_bytes`` and the gauge
    ``nns_slot_state_bytes`` read at the cell's shapes (9 SSM layers, 64
    slots, bfloat16 tails) are the numbers they read with the state stored
    [H, P, N] (PR 36): the benchmark's rooflines rest on the counter."""
    from nnstreamer_tpu import trace as nns_trace

    cfg = gh.config_from_options({"n_layers": "10", "experts_held": "36",
                                  "vocab": "50176"})
    fam = gh.GraniteHybridFamily(cfg, jnp.bfloat16)
    arena = jax.eval_shape(lambda: fam.arena(64, 16, False, 64))
    state, tails = arena[-fam.slot_leaves:]
    assert state.shape == (9, 65, 64, 128, 128) and state.dtype == jnp.float32
    # the batcher's own arithmetic (serving.py: a slot's row over the slot leaves)
    per_slot = sum(math.prod(leaf.shape) * leaf.dtype.itemsize // leaf.shape[1]
                   for leaf in (state, tails))
    assert per_slot == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 38_204_928
    assert per_slot * 64 == 2_445_115_392               # the gauge at 64 slots
    seen = []
    monkeypatch.setattr(nns_trace, "instant",
                        lambda name, **attrs: seen.append((name, attrs)))
    counts = dict(zip(gh.AUX_NAMES, (640, 3200, 360, 6400, 576)))
    fam.note_aux(counts, None)
    update = dict(seen)["nns.state.update"]
    assert update == {"slot_layers": 576, "bytes": 576 * 8_388_608}


# -- served from a launch string ---------------------------------------------

LAUNCH = ("d_model:64,n_heads:4,n_kv_heads:2,head_dim:16,ssm_heads:8,ssm_head_dim:8,"
          "ssm_state:16,ssm_chunk:8,attn_layers:2,d_expert:32,d_shared:48,n_routed:8,"
          "topk:3,attn_scale:0.0625,n_layers:4,experts_held:4,expert_offset:4,vocab:97,"
          "dtype:float32,seed:11")


def _serve(prompts, new_tokens, **props):
    from nnstreamer_tpu.elements.llm_serve import LlmServerSink, LlmServerSrc
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.tensors.frame import Frame
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    src = AppSrc(name="in", spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
    out = TensorSink(name="out", **{"max-stored": 64})
    base = {"model": "zoo:granite_hybrid_lm", "custom": LAUNCH, "id": "gh",
            "n-slots": 2, "max-len": 128, "prompt-len": 32, "kv-layout": "paged",
            "pump": 4}
    pipe = Pipeline().chain(src, LlmServerSink(name="llm", **{**base, **props}))
    pipe.chain(LlmServerSrc(name="llmsrc", id="gh"), out)
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
    """``appsrc ! tensor_llm_serversink model=zoo:granite_hybrid_lm``: prompts
    under and over the bucket (chunked prefill), three requests on two slots
    (a slot's row is landed over what the last request left); every served
    token is the reference's best at its position."""
    prompts = [_tokens(i, (n,)) for i, n in enumerate((9, 50, 32))]
    got, stats = _serve(prompts, 10, **{"attn-impl": attn_impl})
    assert stats["family"] == "granite_hybrid" and stats["attn_impl"] == attn_impl
    assert stats["moe_picks"] == stats["moe_tokens"] * 3 > 0
    assert stats["moe_state_updates"] * 4 == stats["moe_tokens"] * 3  # 3 SSM of 4 layers
    for i, p in enumerate(prompts):
        assert len(got[i]) == 10
        _assert_served_is_reference_best(ref, _cfg(), 11, p, got[i])


@pytest.mark.parametrize("props,named", [
    ({"speculate": "4"}, "speculate"),
    ({"cache-dtype": "int8"}, "cache-dtype=int8"),
    ({"kv-layout": "slot"}, "kv-layout=slot"),
    ({"role": "decode"}, "role"),
    ({"checkpoint-every-tokens": "4", "checkpoint-dir": "/tmp/nns-gh-ckpt"},
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
                          family=gh.GraniteHybridFamily(_cfg(), jnp.float32))


def test_state_instant_is_on_the_profilers_timeline(tmp_path):
    """``nns.state.update`` beside ``nns.moe.routing``: one instant each per
    harvested pump, its bytes from THIS family's state; ``nns.pump.launch``
    carries the live lanes' state bytes."""
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
    assert all(u["bytes"] == u["slot_layers"] * 2 * 8 * 8 * 16 * 4 for u in updates)
    launches = [s for name, s in events if name == "nns.pump.launch"]
    per_slot = cfg.n_ssm * (8 * 8 * 16 * 4 + 3 * cfg.conv_width * 4)
    assert launches and all(s["state_bytes"] == s["active"] * per_slot for s in launches)


# -- the accepted expert families' programs are the parent's ------------------

# sha256 of the lowered text of each program at a small size, by this same
# function: the new family imports longcat's and Kimi-Linear's functions and
# edits none, so neither family gets a new compile-cache key (PERF.md section
# 7, 5). The prompt programs, over a bucket of more than ``MOE_DENSE_TOKENS``
# tokens as every served bucket is, were taken on commit 25e2794 and have not
# moved since; the decode programs were taken again when their expert layers
# moved to ``held_experts_ffn`` (PERF.md section 6).
PARENT_PROGRAMS = {
    "longcat.prefill": "ac196f07a3ca1f70e18a7d8ec8f08020c0e87823ed6b7dd83cf8411a4c081dce",
    "longcat.decode": "d5f653f70f423e3969e3e38c659169b2cfabb842f182107dd3060cd748676efa",
    "kimi_linear.prefill": "84dcc7068b104f785fd8c7df5f45fcdcf5d11b4fb9ecbccb9038ca5afcb5bd78",
    "kimi_linear.decode": "e4051ab82488177e5af0f90d9ce7615fd0264f7f63ac0850e4a073cfb50e7b01",
}
PROMPT = 160  # a bucket that takes the grouped matmul


def lowered_programs():
    """name -> sha256 of the lowered (StableHLO) text of the two accepted
    expert families' prefill and decode programs at a small size."""
    from nnstreamer_tpu.models import kimi_linear as kl
    from nnstreamer_tpu.models import longcat as lc

    out = {}
    fams = {
        "longcat": (lc, lc.LongcatConfig(
            d_model=64, n_heads=4, q_rank=32, kv_rank=16, nope=8, rope=8, v_dim=8,
            d_ff=128, d_expert=32, n_routed=8, n_zero=4, topk=2, n_layers=2,
            vocab=97, n_held=4, expert_offset=4)),
        "kimi_linear": (kl, kl.KimiLinearConfig(
            d_model=64, n_heads=4, kv_rank=16, nope=8, rope=8, v_dim=8, kda_heads=4,
            kda_dim=16, gate_rank=8, d_ff=128, d_expert=32, n_routed=8, topk=2,
            n_layers=5, vocab=97, n_held=4, expert_offset=4)),
    }
    for name, (mod, cfg) in fams.items():
        fam = next(v for k, v in vars(mod).items() if k.endswith("Family"))(
            cfg, jnp.float32)
        params = jax.eval_shape(lambda: mod.init_params(cfg, 0, jnp.float32))
        arena = jax.eval_shape(lambda: fam.arena(8, 16, False, 2))
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        texts = {
            "prefill": jax.jit(fam.prefill).lower(params, i32((1, PROMPT))).as_text(),
            "decode": jax.jit(fam.decode_step).lower(
                params, i32((2,)), i32((2,)), jax.ShapeDtypeStruct((2,), jnp.bool_),
                arena, i32((2, 4))).as_text(),
        }
        for prog, text in texts.items():
            out[f"{name}.{prog}"] = hashlib.sha256(text.encode()).hexdigest()
    return out


@pytest.fixture(scope="module")
def programs_now():
    return lowered_programs()


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_accepted_expert_families_lower_to_the_parents_text(programs_now, program):
    assert programs_now[program] == PARENT_PROGRAMS[program]
