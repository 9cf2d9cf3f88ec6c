"""The decode step's expert kernel (ops/pallas/held_experts.py): parity with its
jnp reference over tokens, held experts and how many of them a step hit
(interpret mode), padded grid slots that add nothing and experts no token
picked that are never read; LongCat-Flash's and Kimi-Linear's decode ``moe``
on the kernel against their bucket and dense paths, Granite-4.0-H's left on
``experts_dense``; and the benchmark's readers of the kernel's roofline on
synthetic traces."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.ops.pallas import held_experts as he

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _operands(t, n, hit, d=128, f=256, seed=0):
    """x [T, d], gates [T, n] in which exactly the experts ``hit`` have a
    token (a token picks one to three of them), and the stacked weights."""
    rng = np.random.default_rng(seed)
    gates = np.zeros((t, n), np.float32)
    for e in hit:
        gates[rng.integers(0, t), e] = rng.uniform(0.2, 1.5)
    for tok in range(t):
        if hit:
            for e in rng.choice(hit, size=min(len(hit), rng.integers(1, 4)), replace=False):
                gates[tok, e] = rng.uniform(0.2, 1.5)
    w = lambda *s: rng.standard_normal(s).astype(np.float32) / np.sqrt(s[-2])  # noqa: E731
    return (rng.standard_normal((t, d)).astype(np.float32), gates,
            w(n, d, f), w(n, d, f), w(n, f, d))


@pytest.mark.parametrize("t,n,hit", [
    (1, 1, []),                 # no expert hit: every slot skipped
    (1, 1, [0]),
    (8, 4, [2]),                # one expert hit, three padded slots
    (13, 6, [5, 0, 3]),         # a token count off the sublane tile
    (16, 5, [0, 1, 2, 3, 4]),   # every expert hit, no padding
])
def test_kernel_equals_its_reference(t, n, hit):
    """Two f-tiles an expert; the weights of every expert no token picked are
    NaN, so a slot that read or added one would show."""
    x, gates, wg, wu, wd = _operands(t, n, hit)
    cold = [e for e in range(n) if e not in hit]
    poisoned = [w.copy() for w in (wg, wu, wd)]
    for w in poisoned:
        w[cold] = np.nan
    want, want_sizes = he.held_experts_ffn_ref(*map(jnp.asarray, (x, gates, wg, wu, wd)))
    got, sizes = he.held_experts_ffn(jnp.asarray(x), jnp.asarray(gates),
                                     *map(jnp.asarray, poisoned), tile=128,
                                     interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(want_sizes))
    assert sorted(np.flatnonzero(np.asarray(sizes))) == sorted(hit)
    if not hit:
        assert float(jnp.max(jnp.abs(got))) == 0.0


def test_hit_list_is_the_picked_experts_then_the_last_repeated():
    gates = jnp.zeros((3, 6)).at[0, 4].set(1.0).at[2, 1].set(0.5).at[1, 4].set(0.2)
    ids, count, sizes = he.hit_list(gates)
    assert list(np.asarray(ids)) == [1, 4, 4, 4, 4, 4]
    assert int(count[0]) == 2 and list(np.asarray(sizes)) == [0, 1, 0, 0, 2, 0]
    ids, count, _ = he.hit_list(jnp.zeros((3, 6)))
    assert int(count[0]) == 0 and not np.asarray(ids).any()


def test_the_padded_slots_fetch_nothing_new():
    """Every grid step past the hit list's length maps each weight block to
    the last real step's block: the pipeline issues no DMA for them."""
    from nnstreamer_tpu.ops.pallas import registry

    spec = registry.get("held_experts_ffn")
    plan = spec.plan({"t": 8, "d": 128, "f": 256, "n": 5, "hit": [4, 1], "tile": 128})
    ids, count = (p.make() for p in plan.prefetch)
    for blk in plan.blocks:
        seen = [tuple(int(v) for v in blk.index_map(i, j, ids, count))
                for i in range(plan.grid[0]) for j in range(plan.grid[1])]
        real = len(seen) - (plan.grid[0] - 2) * plan.grid[1]
        assert set(seen[real - 1:]) == {seen[real - 1]}, blk.name


def test_tiles_fit_the_budget_at_the_cells_widths():
    """LongCat-Flash (64 slots of 6144, experts of 2048) and Kimi-Linear (128
    slots of 2304, experts of 1024) in bfloat16: whole lanes, and the step's
    blocks double-buffered beside x and the accumulator under 16 MiB."""
    for t, d, f, want in ((64, 6144, 2048, 128), (128, 2304, 1024, 512)):
        tf = he.f_tile(t, d, f, 2)
        assert tf == want and f % tf == 0
        assert 6 * d * tf * 2 + t * d * 6 <= he.VMEM_BUDGET


def test_registry_covers_the_kernel():
    from nnstreamer_tpu.analysis.selfcheck import kscope_self_check
    from nnstreamer_tpu.ops import dispatch
    from nnstreamer_tpu.ops.pallas import registry

    spec = registry.get("held_experts_ffn")
    assert spec.ops == ("moe_experts",) and "moe_experts" in dispatch.KNOWN_OPS
    assert {c.name for c in spec.tier1_cases()} >= {"none-hit", "all-hit"}
    assert kscope_self_check() == []
    for case in spec.tier1_cases():
        got, want, atol = spec.run_case(dict(case.params))
        assert float(jnp.max(jnp.abs(got - want))) < atol, case.name


# -- the three families' decode expert layers ---------------------------------


@pytest.mark.parametrize("n_held,offset", [(4, 4), (16, 0), (1, 15)])
def test_longcat_decode_moe_on_the_kernel_equals_dispatch_held(monkeypatch, n_held,
                                                               offset):
    """The same routing through the kernel (a decode step's tokens) and
    through the grouped matmul (a bucket's path): the same layer, the same
    counters."""
    from nnstreamer_tpu.models import longcat as lc

    cfg = lc.LongcatConfig(d_model=64, n_heads=4, q_rank=16, kv_rank=8, nope=8,
                           rope=8, v_dim=8, d_ff=128, d_expert=32, n_routed=16,
                           n_zero=8, topk=3, n_layers=2, vocab=97, n_held=n_held,
                           expert_offset=offset)
    lp = lc.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)), jnp.float32)
    live = jnp.ones((12,), bool).at[3].set(False)
    got, stats = lc.moe(b, live, lp, cfg)
    monkeypatch.setattr(lc, "MOE_DENSE_TOKENS", 0)
    want, want_stats = lc.moe(b, live, lp, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))


def test_kimi_linear_decode_moe_on_the_kernel_equals_experts_dense():
    from nnstreamer_tpu.models import kimi_linear as kl

    cfg = kl.KimiLinearConfig(
        d_model=64, n_heads=4, kv_rank=16, nope=8, rope=8, v_dim=8, kda_heads=4,
        kda_dim=16, gate_rank=8, d_ff=128, d_expert=32, n_routed=8, topk=2,
        n_layers=5, vocab=97, n_held=4, expert_offset=2)
    lp = kl.init_params(cfg, 4, jnp.float32)["layers"][1]
    b = jnp.asarray(np.random.default_rng(6).normal(size=(10, 64)), jnp.float32)
    live = jnp.ones((10,), bool).at[0].set(False)
    got, stats = kl.moe(b, live, lp, cfg, shared=False)
    idx, w = kl.route(b, lp, cfg)
    local = live[:, None] & (idx >= 2) & (idx < 6)
    want, sizes = kl.experts_dense(b, w, local, jnp.where(local, idx - 2, 4), lp, 4)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert int(stats[2]) == int(jnp.sum(sizes > 0))


def test_granite_decode_moe_stays_on_experts_dense(monkeypatch):
    """Granite-4.0-H's routing hits nearly every held expert: its decode step
    keeps the dense form, and the result is that form's to the bit."""
    from nnstreamer_tpu.models import granite_hybrid as gh
    from nnstreamer_tpu.models import kimi_linear as kl

    cfg = gh.GraniteHybridConfig(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8,
        ssm_head_dim=8, ssm_state=16, ssm_chunk=8, attn_layers=(2,), d_expert=32,
        d_shared=48, n_routed=8, topk=3, attn_scale=0.0625, n_layers=4, vocab=97,
        n_held=4, expert_offset=0)
    lp = gh.init_params(cfg, 4, jnp.float32)["layers"][0]
    b = jnp.asarray(np.random.default_rng(7).normal(size=(9, 64)), jnp.float32)
    live = jnp.ones((9,), bool)
    calls = []

    def dense(*args):
        calls.append(args)
        return kl.experts_dense(*args)

    monkeypatch.setattr(gh, "experts_dense", dense)
    got, _ = gh.moe(b, live, lp, cfg, shared=False)
    idx, w = gh.route(b, lp, cfg)
    local = live[:, None] & (idx < 4)
    want, _ = kl.experts_dense(b, w, local, jnp.where(local, idx, 4), lp, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(calls) == 1


# -- the benchmark's readers, on synthetic traces -----------------------------

MS = 1e6  # ns


def _ctx(config, events, ops, launches=10.0, window=(0.0, 1000 * MS)):
    from benchmark.lib import host_spans, shapes

    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        sizes = shapes.sizes_of(json.load(f))
    host_spans._parsed[tuple(window)] = {"launches": [], "events": [
        {"name": name, "line": "l", "start_ns": 1.0, "end_ns": 1.0, "stats": st}
        for name, st in events]}
    return {"trace": {"window_ns": window, "ops": ops,
                      "modules": {"jit_impl(7)": [0.9, launches],
                                  "jit_nns_prefill(3)": [0.05, 2.0]}},
            "trace_names": {"decode": ["jit_impl"]}, "pump": 8, "sizes": sizes,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}}


@pytest.fixture
def parsed():
    from benchmark.lib import host_spans

    yield
    host_spans._parsed.clear()


def test_longcat_expert_roofline_reads_hit_bytes_over_kernel_seconds(parsed):
    """10 of 16 experts hit in each of 4 layers: 40 x 75.5 MB a step, 3.687 ms
    at 819 GB/s; the kernel's ops 0.328 s over 10 launches of 8 steps."""
    from benchmark.layer_metrics import moe_expert_roofline_pct as m

    routing = [("nns.moe.routing", {"tokens": 512, "local_pairs": 1536,
                                    "experts_hit": 320, "zero_picks": 0,
                                    "picks": 6144})] * 10
    ctx = _ctx("longcat-flash-chat", routing,
               {"held_experts_ffn.1": 0.2, "held_experts_ffn.2": 0.128, "fusion.3": 0.4})
    least = 40 * 3 * 6144 * 2048 * 2 / 819e9
    assert m.read(ctx) == pytest.approx(100 * least / (0.328 / 80))
    ctx["trace"]["ops"] = {"ragged-dot-none.1": 0.33}                # the parent
    assert m.read(ctx) is None


def test_kimi_linear_expert_roofline_reads_hit_bytes_over_kernel_seconds(parsed):
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "moe_expert_roofline_pct.hybrid.py")
    spec = importlib.util.spec_from_file_location("moe_expert_roofline_hybrid", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    routing = [("nns.moe.routing", {"tokens": 1024, "local_pairs": 2048,
                                    "experts_hit": 2880, "picks": 8192}),
               ("nns.state.update", {"slot_layers": 7168})] * 5
    ctx = _ctx("kimi-linear-48b-a3b", routing, {"held_experts_ffn": 0.31},
               launches=5.0)
    least = 360 * 3 * 2304 * 1024 * 2 / 819e9   # 45 of 64 hit in 8 layers
    assert m.read(ctx) == pytest.approx(100 * least / (0.31 / 40))
    ctx["trace"]["ops"] = {"fusion.12": 0.5}                          # the parent
    assert m.read(ctx) is None
