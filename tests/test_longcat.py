"""LongCat-Flash block family (models/longcat.py) against its plain reference
(benchmark/reference/longcat_flash.py) at a small size on the CPU, float32
storage, seeded: the full forward, prefill then decode through the paged
latent cache, absorbed against un-absorbed attention, the router's rules, the
shares of a layer adding up to the uncut layer, serving from a launch string,
and the properties that refuse by name."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.kv.block_attn import write_fresh_window
from nnstreamer_tpu.models import longcat as lc
from nnstreamer_tpu.models.serving import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(d_model=64, n_heads=4, q_rank=16, kv_rank=8, nope=8, rope=8, v_dim=8,
             d_ff=128, d_expert=32, n_routed=16, n_zero=8, topk=3, n_layers=2,
             vocab=97)
TOL = 2e-5  # float32 on the CPU: two sound orders of summation, logits of size ~4


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("ref_longcat_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(n_held=4, offset=4, **over):
    return lc.LongcatConfig(**{**SIZES, **over}, n_held=n_held, expert_offset=offset)


def _shape(cfg):
    """The reference's own description of the same configuration."""
    return dict(d=cfg.d_model, heads=cfg.n_heads, q_rank=cfg.q_rank,
                kv_rank=cfg.kv_rank, nope=cfg.nope, rope=cfg.rope, v_dim=cfg.v_dim,
                d_ff=cfg.d_ff, d_expert=cfg.d_expert, n_routed=cfg.n_routed,
                n_zero=cfg.n_zero, topk=cfg.topk, scale=cfg.scale, theta=cfg.theta,
                eps=cfg.eps, n_layers=cfg.n_layers, vocab=cfg.vocab,
                n_held=cfg.n_held, expert_offset=cfg.expert_offset)


def _tokens(seed, shape, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(np.int32)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "longcat_flash.py")) as f:
        assert "nnstreamer_tpu" not in f.read().split('"""', 2)[2]


@pytest.mark.parametrize("seed,n_held,offset", [(11, 4, 4), (5, 16, 0), (7, 1, 15)])
def test_full_forward_matches_reference(ref, seed, n_held, offset):
    cfg = _cfg(n_held, offset)
    params = lc.init_params(cfg, seed, jnp.float32)
    toks = _tokens(seed, (3, 24))
    got = lc.apply(params, jnp.asarray(toks), cfg)
    want = ref.logits(_shape(cfg), seed, toks, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_padding_routes_nowhere_and_changes_nothing_before_it(ref):
    cfg = _cfg()
    params = lc.init_params(cfg, 3, jnp.float32)
    toks = _tokens(3, (1, 20))
    padded = np.full((1, 32), -1, np.int32)
    padded[:, :20] = toks
    got = lc.apply(params, jnp.asarray(padded), cfg)[:, :20]
    want = ref.logits(_shape(cfg), 3, toks, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("seed", [2, 9])
def test_prefill_then_decode_through_paged_latents_matches_reference(ref, seed):
    """Prompt latents land in arena blocks through the tables; every later
    token is one ``decode_step`` off the arena (teacher-forced); its logits
    are the reference's full forward at that position. Slot 2 is dead."""
    cfg = _cfg()
    params = lc.init_params(cfg, seed, jnp.float32)
    fam = lc.LongcatFamily(cfg, jnp.float32)
    bs, nb, n_prompt, n_new = 16, 4, (19, 32, 7), 9
    full = _tokens(seed, (3, 32 + n_new))
    want = np.asarray(ref.logits(_shape(cfg), seed, full, "float32"))
    arena = fam.arena(3 * nb, bs)
    # scattered physical blocks, none of them scratch block 0
    tables = jnp.asarray(1 + np.random.default_rng(seed).permutation(3 * nb)
                         .reshape(3, nb).astype(np.int32))
    active = jnp.asarray([True, True, False])
    pos = jnp.asarray(n_prompt, jnp.int32)
    for b, n in enumerate(n_prompt):
        _, lat = lc.prefill(params, jnp.asarray(full[b:b + 1, :n]), cfg, jnp.float32)
        arena = write_fresh_window(
            arena, tables[b:b + 1], lat, jnp.zeros((1,), jnp.int32), n,
            jnp.asarray([True]), False)
    step = jax.jit(lambda tok, pos, arena: lc.decode_step(
        params, tok, pos, active, arena, tables, cfg))
    for j in range(n_new):
        tok = jnp.asarray([full[b, n_prompt[b] + j] for b in range(3)], jnp.int32)
        logits, arena, pos2, stats = step(tok, pos, arena)
        for b in (0, 1):
            err = np.max(np.abs(np.asarray(logits[b]) - want[b, n_prompt[b] + j]))
            assert err < TOL, (b, j, err)
        assert np.array_equal(np.asarray(pos2 - pos), [1, 1, 0])
        assert int(stats[0]) == 2 * cfg.n_layers and int(stats[4]) == 2 * cfg.n_layers * cfg.topk
        pos = pos2
    assert float(jnp.max(jnp.abs(arena[0][:, 0]))) == 0.0  # scratch stays pristine


def test_absorbed_attention_equals_unabsorbed():
    cfg = _cfg()
    sp = lc.init_params(cfg, 1, jnp.float32)["layers"][0]["sub"][1]
    rng = np.random.default_rng(0)
    b, t, s = 2, 5, 23
    q_nope = jnp.asarray(rng.normal(size=(b, t, cfg.n_heads, cfg.nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, t, cfg.n_heads, cfg.rope)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(b, s, cfg.kv_rank)), jnp.float32)
    k_r = jnp.asarray(rng.normal(size=(b, s, cfg.rope)), jnp.float32)
    mask = jnp.asarray(rng.random((b, t, s)) < 0.7).at[:, :, 0].set(True)
    want = lc.mla_attend_expanded(q_nope, q_rope, lat, k_r, sp, cfg, mask)
    kr_pad = jnp.pad(k_r, ((0, 0), (0, 0), (0, cfg.kr_width - cfg.rope)))
    got = lc.mla_attend_absorbed(lc.mla_absorb_q(q_nope, sp), q_rope, lat, kr_pad,
                                 sp, cfg, mask)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_router_bias_moves_the_choice_and_not_the_weight():
    cfg = _cfg()
    lp = dict(lc.init_params(cfg, 4, jnp.float32)["layers"][0])
    b = jnp.asarray(np.random.default_rng(1).normal(size=(6, cfg.d_model)), jnp.float32)
    idx0, w0 = lc.route(b, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, cfg)
    never = int(np.setdiff1d(np.arange(24), np.asarray(idx0))[0])
    bias = jnp.zeros((24,), jnp.float32).at[never].set(10.0)
    idx1, w1 = lc.route(b, {**lp, "router_bias": bias}, cfg)
    assert bool(jnp.all(jnp.any(idx1 == never, axis=-1)))   # now always chosen
    p = jax.nn.softmax(jnp.einsum("td,dr->tr", b, lp["router"], precision="highest"))
    chosen = jnp.take_along_axis(p, idx1, axis=-1) * cfg.scale
    assert float(jnp.max(jnp.abs(w1 - chosen))) < 1e-6      # weight is scale * p, unbiased
    assert float(jnp.sum(w1, -1).max()) < cfg.scale         # and not renormalised


def test_identity_experts_add_w_times_b():
    """A share that holds an expert no token picks adds exactly the identity term."""
    cfg = _cfg(n_held=1, offset=0)
    lp = dict(lc.init_params(cfg, 4, jnp.float32)["layers"][0])
    lp["router_bias"] = jnp.zeros((24,), jnp.float32).at[0].set(-10.0)
    b = jnp.asarray(np.random.default_rng(2).normal(size=(5, cfg.d_model)), jnp.float32)
    s, stats = lc.moe(b, jnp.ones((5,), bool), lp, cfg)
    idx, w = lc.route(b, lp, cfg)
    ident = jnp.sum(jnp.where(idx >= cfg.n_routed, w, 0.0), -1)
    assert int(stats[1]) == 0 and int(stats[2]) == 0
    assert int(stats[3]) == int(jnp.sum(idx >= cfg.n_routed)) > 0
    assert float(jnp.max(jnp.abs(s - ident[:, None] * b))) < 1e-6


def test_dead_tokens_reach_no_expert():
    cfg = _cfg(n_held=16, offset=0)
    lp = lc.init_params(cfg, 4, jnp.float32)["layers"][0]
    b = jnp.asarray(np.random.default_rng(2).normal(size=(6, cfg.d_model)), jnp.float32)
    live = jnp.asarray([True, False, True, False, False, True])
    s, stats = lc.moe(b, live, lp, cfg)
    assert float(jnp.max(jnp.abs(s[~live]))) == 0.0
    assert (int(stats[0]), int(stats[4])) == (3, 3 * cfg.topk)
    alone, _ = lc.moe(b[live], jnp.ones((3,), bool), lp, cfg)
    assert float(jnp.max(jnp.abs(s[live] - alone))) < 1e-5


@pytest.mark.parametrize("n_shares", [4, 16])
def test_the_shares_add_up_to_the_uncut_layer(ref, n_shares):
    """One layer computed by every share of its routed experts: the shares'
    outputs, with what every chip computes alike (attention, dense FFNs, the
    identity experts) counted once, add up to the uncut reference's layer."""
    seed, held = 13, 16 // n_shares
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 12, 64)) * 0.5, jnp.float32)
    live = jnp.ones((2, 12), bool)
    positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    mask = jnp.broadcast_to((jnp.arange(12)[:, None] >= jnp.arange(12))[None], (2, 12, 12))
    outs = []
    for k in range(n_shares):
        cfg = _cfg(held, k * held)
        lp = lc.init_params(cfg, seed, jnp.float32)["layers"][1]

        def attend(i, a, sp):
            q_nope, q_rope, lat, k_r = lc.mla_project(a, sp, cfg, positions)
            return lc.mla_attend_expanded(q_nope, q_rope, lat, k_r, sp, cfg, mask)

        outs.append(lc._layer(x, lp, cfg, live, attend)[0])
    shape = _shape(_cfg(16, 0))
    alike = ref.layer(x[None], shape, seed, 1, "float32", n_held=0, expert_offset=0)[0]
    whole = ref.layer(x[None], shape, seed, 1, "float32", n_held=16, expert_offset=0)[0]
    summed = alike + sum(o - alike for o in outs)
    # values of size ~6 through 2 * n_shares float32 subtractions and sums
    assert float(jnp.max(jnp.abs(summed - whole))) < 2e-4
    assert float(jnp.max(jnp.abs(whole - alike))) > 1e-2  # the experts do add something


LAUNCH = ("d_model:64,n_heads:4,q_rank:16,kv_rank:8,nope:8,rope:8,v_dim:8,d_ff:128,"
          "d_expert:32,n_routed:16,n_zero:8,topk:3,n_layers:2,experts_held:4,"
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
    base = {"model": "zoo:longcat_flash_lm", "custom": LAUNCH, "id": "lc", "n-slots": 2,
            "max-len": 128, "prompt-len": 32, "kv-layout": "paged", "pump": 4}
    pipe = Pipeline().chain(src, LlmServerSink(name="llm", **{**base, **props}))
    pipe.chain(LlmServerSrc(name="llmsrc", id="lc"), out)
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


def test_served_from_a_launch_string_through_the_paged_batcher(ref):
    """``appsrc ! tensor_llm_serversink model=zoo:longcat_flash_lm``: prompts
    under and over the bucket (chunked prefill), three requests on two slots;
    every served token is the reference's best at its position."""
    prompts = [_tokens(i, (n,)) for i, n in enumerate((9, 50, 32))]
    got, stats = _serve(prompts, 10)
    assert stats["family"] == "longcat_flash" and "kv_attn" not in stats
    assert stats["moe_picks"] == stats["moe_tokens"] * 3 > 0
    shape = _shape(_cfg())
    for i, p in enumerate(prompts):
        assert len(got[i]) == 10
        z = np.asarray(ref.logits(shape, 11, np.concatenate([p, got[i]])[None], "float32"))[0]
        for j, tok in enumerate(got[i]):
            row = z[len(p) - 1 + j]
            assert row.max() - row[tok] < TOL, (i, j)


@pytest.mark.parametrize("props,named", [
    ({"speculate": "4"}, "speculate"),
    ({"cache-dtype": "int8"}, "cache-dtype=int8"),
    ({"kv-layout": "slot"}, "kv-layout=slot"),
    ({"role": "decode"}, "role"),
    ({"checkpoint-every-tokens": "4", "checkpoint-dir": "/tmp/nns-lc-ckpt"},
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
])
def test_batcher_refuses_what_the_family_does_not_carry(call, named):
    cfg = _cfg()
    cb = ContinuousBatcher(lc.init_params(cfg, 0, jnp.float32), cfg.n_heads, n_slots=2,
                           max_len=64, prompt_len=32, kv_layout="paged",
                           family=lc.LongcatFamily(cfg, jnp.float32))
    with pytest.raises(ValueError, match=named):
        call(cb)
    with pytest.raises(ValueError, match="windowed"):
        ContinuousBatcher({}, 4, kv_layout="paged", windowed=True,
                          family=lc.LongcatFamily(cfg, jnp.float32))


def test_prefix_sharing_works_on_latent_blocks(ref):
    """A registered prefix's latent blocks are adopted by a later request
    (chunked prefill attends them through the stage) and the stream is the
    reference's."""
    cfg = _cfg()
    cb = ContinuousBatcher(lc.init_params(cfg, 11, jnp.float32), cfg.n_heads, n_slots=2,
                           max_len=128, prompt_len=32, kv_layout="paged",
                           family=lc.LongcatFamily(cfg, jnp.float32))
    prefix, tail = _tokens(1, (40,)), _tokens(2, (13,))
    cb.register_prefix(prefix)
    rid = cb.submit(np.concatenate([prefix, tail]), 8)
    while cb.result(rid) is None:
        cb.step_pump(4)
    toks = cb.result(rid)
    assert cb.stats()["kv_prefix_hit_tokens"] >= 32
    full = np.concatenate([prefix, tail, np.asarray(toks, np.int32)])[None]
    z = np.asarray(ref.logits(_shape(cfg), 11, full, "float32"))[0]
    for j, tok in enumerate(toks):
        row = z[52 + j]
        assert row.max() - row[tok] < TOL


@pytest.mark.parametrize("fills", [(19, 32, 0), (1, 47, 64)])
def test_decode_step_kernel_equals_xla_oracle(fills):
    """The block-table kernel (interpret mode) in the step's place of the XLA
    view path: ragged fills, a dead slot with a stale table, NaN in every
    block no live slot owns."""
    import functools

    from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_decode_attention

    cfg = _cfg()
    params = lc.init_params(cfg, 6, jnp.float32)
    fam = lc.LongcatFamily(cfg, jnp.float32)
    bs, nb = 16, 4
    rng = np.random.default_rng(0)
    tables = 1 + rng.permutation(3 * nb).reshape(3, nb).astype(np.int32)
    pos = np.asarray(fills, np.int32)
    active = pos > 0
    lat, kr = (np.asarray(a).copy() for a in fam.arena(3 * nb, bs))
    lat[:, 1:], kr[:, 1:] = np.nan, np.nan
    for b, n in enumerate(pos):
        for t in range(int(n)):
            blk, off = tables[b, t // bs], t % bs
            lat[:, blk, off] = rng.normal(size=lat.shape[0:1] + lat.shape[3:])
            kr[:, blk, off] = 0.0
            kr[:, blk, off, :cfg.rope] = rng.normal(size=(kr.shape[0], cfg.rope))
    arena = (jnp.asarray(lat), jnp.asarray(kr))
    tok = jnp.asarray(_tokens(1, (3,)))
    args = (params, tok, jnp.asarray(pos), jnp.asarray(active), arena,
            jnp.asarray(tables), cfg)
    want = lc.decode_step(*args)
    got = lc.decode_step(*args, attn_fn=functools.partial(
        mla_paged_decode_attention, interpret=True, chunk=2))
    live = np.asarray(active)
    assert float(jnp.max(jnp.abs(got[0][live] - want[0][live]))) < TOL
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))


def test_routing_instant_is_on_the_profilers_timeline(tmp_path):
    """``nns.moe.routing``: one instant per harvested pump, its attributes the
    counters the pump carried home in its one readback."""
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
    events = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "nns.moe.routing"]
    assert events
    for key in lc.MOE_STATS:
        assert sum(e[key] for e in events) == stats["moe_" + key]
    assert all(e["picks"] == e["tokens"] * 3 for e in events)


@pytest.mark.parametrize("n_held,offset,path", [(1, 3, "few"), (16, 0, "all")])
def test_many_pairs_take_the_short_grouped_matmul_only_when_it_holds_them(
        monkeypatch, n_held, offset, path):
    """Above ``MOE_FEW_PAIRS`` pairs the layer first tries an eighth of the
    rows; both branches give what the single grouped matmul gives."""
    cfg = _cfg(n_held, offset)
    lp = lc.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(5).normal(size=(40, cfg.d_model)), jnp.float32)
    live = jnp.ones((40,), bool).at[7].set(False)
    want, stats = lc.moe(b, live, lp, cfg)
    monkeypatch.setattr(lc, "MOE_FEW_PAIRS", 16)
    assert (int(stats[1]) <= 40 * cfg.topk // 8) == (path == "few")
    got, stats2 = lc.moe(b, live, lp, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert np.array_equal(np.asarray(stats), np.asarray(stats2))
