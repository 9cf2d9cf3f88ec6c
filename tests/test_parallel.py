"""Parallel-layer tests on the virtual 8-device CPU mesh: ring attention,
Ulysses, MoE expert parallelism, pipeline parallelism, dp×sp×ep LM step.

The reference has no collective backend (SURVEY.md §2.6); these validate
the genuinely-new TPU-native scaling layer. Numeric checks compare every
sharded path against its single-device dense reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.parallel import lm, moe
from nnstreamer_tpu.parallel import pipeline_parallel as pp
from nnstreamer_tpu.parallel import ring_attention as ra
from nnstreamer_tpu.parallel import ulysses
from nnstreamer_tpu.parallel.mesh import make_mesh


def _qkv(rng, b=2, t=64, h=8, d=16):
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32) for _ in range(3)
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(0))
        out_ring = ra.make_ring_attention(mesh, "sp", causal=causal)(q, k, v)
        out_dense = ra.dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out_ring, out_dense, atol=2e-5)

    def test_grad_flows(self):
        # the ring loop is a scan over ppermute — reverse-differentiable
        mesh = make_mesh(4, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(1), t=16, h=2, d=8)
        ring = ra.make_ring_attention(mesh, "sp", causal=True)

        g = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2))(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(ra.dense_attention(q, k, v, causal=True) ** 2)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_kv_chunked_matches_dense(self, causal):
        """kv_chunk bounds the in-shard score tensor; numerics must match
        the unchunked ring and the dense reference."""
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(3))
        out_c = ra.make_ring_attention(mesh, "sp", causal=causal, kv_chunk=2)(
            q, k, v
        )
        out_dense = ra.dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out_c, out_dense, atol=2e-5)

    def test_kv_chunk_must_divide(self):
        with pytest.raises(ValueError, match="chunk"):
            b = jnp.zeros((1, 6, 1, 4), jnp.float32)
            ra._online_block_chunked(
                b, b, b, jnp.ones((6, 6), bool),
                jnp.full((1, 1, 6), ra.NEG_INF), jnp.zeros((1, 1, 6)),
                jnp.zeros((1, 6, 1, 4)), 0.5, chunk=4,
            )

    def test_kv_chunked_grad_matches_dense(self):
        """Backward through the chunked nested scan must equal dense."""
        mesh = make_mesh(4, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(5), t=16, h=2, d=8)
        ring = ra.make_ring_attention(mesh, "sp", causal=True, kv_chunk=2)
        g = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2))(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(ra.dense_attention(q, k, v, causal=True) ** 2)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd), atol=2e-5)

    def test_kv_chunk_rejects_nonpositive(self):
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(4), t=16, h=1, d=4)
        with pytest.raises(ValueError, match="positive divisor"):
            ra.make_ring_attention(mesh, "sp", kv_chunk=0)(q, k, v)

    def test_kv_chunk_rejected_for_ulysses(self):
        mesh = make_mesh(8, axes=("dp", "sp"), shape=(2, 4))
        with pytest.raises(ValueError, match="ring"):
            lm._make_attn_fn(mesh, "ulysses", "dp", "sp", kv_chunk=4)

    def test_fully_masked_rows_are_zero(self):
        # row 0 of a causal block attends only to itself; a remote-only
        # shard sees fully-masked blocks and must contribute exact zeros
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(2), t=8, h=1, d=4)
        out = ra.make_ring_attention(mesh, "sp", causal=True)(q, k, v)
        assert np.all(np.isfinite(np.asarray(out)))


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(3))  # 8 heads % 8 devices
        out_u = ulysses.make_ulysses_attention(mesh, "sp", causal=causal)(q, k, v)
        out_d = ra.dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out_u, out_d, atol=2e-5)

    def test_rejects_indivisible_heads(self):
        mesh = make_mesh(8, axes=("sp",))
        q, k, v = _qkv(np.random.default_rng(4), h=6)
        with pytest.raises(Exception):
            ulysses.make_ulysses_attention(mesh, "sp")(q, k, v)


class TestMoE:
    def test_ep_matches_dense(self):
        rng = np.random.default_rng(5)
        mp = moe.init_moe_params(
            jax.random.PRNGKey(1), d_model=32, d_ff=64, n_experts=8, n_layers=1
        )
        mp0 = jax.tree.map(lambda a: a[0], mp)
        x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        y_dense = moe.moe_ffn_dense(x, mp0, top_k=2)
        mesh = make_mesh(8, axes=("ep",))
        f = jax.jit(
            jax.shard_map(
                functools.partial(moe.moe_ffn_local, axis_name="ep", top_k=2),
                mesh=mesh,
                in_specs=(P(), {"gate": P(), "w_in": P("ep"), "w_out": P("ep")}),
                out_specs=P(),
                check_vma=False,
            )
        )
        y_ep = f(x, mp0)
        np.testing.assert_allclose(y_ep, y_dense, atol=1e-5)

    def test_topk_gate_sparsity(self):
        mp = moe.init_moe_params(
            jax.random.PRNGKey(2), d_model=8, d_ff=16, n_experts=4, n_layers=1
        )
        x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 5, 8)), jnp.float32)
        probs = np.asarray(moe.gate_probs(x, mp["gate"][0], top_k=2))
        nonzero = (probs > 0).sum(axis=-1)
        assert np.all(nonzero == 2)
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-6)


class TestPipelineParallel:
    def test_matches_sequential(self):
        mesh = make_mesh(8, axes=("pp",))
        rng = np.random.default_rng(7)
        stack = tfm.init_params(
            jax.random.PRNGKey(3), vocab=32, d_model=32, n_heads=4, n_layers=8
        )["blocks"]
        xs = jnp.asarray(rng.standard_normal((16, 12, 32)), jnp.float32)
        positions = jnp.arange(12)

        def stage(x_mb, sp_):
            return tfm.apply_layers(sp_, x_mb, 4, positions)

        y_seq = tfm.apply_layers(stack, xs, 4, positions)
        y_pp = pp.make_pipeline_forward(mesh, stage, n_microbatches=4)(stack, xs)
        np.testing.assert_allclose(y_pp, y_seq, atol=2e-4)

    def test_rejects_ragged_microbatch(self):
        mesh = make_mesh(4, axes=("pp",))
        stack = tfm.init_params(
            jax.random.PRNGKey(4), vocab=16, d_model=16, n_heads=2, n_layers=4
        )["blocks"]
        xs = jnp.zeros((10, 4, 16), jnp.float32)
        with pytest.raises(Exception):
            pp.make_pipeline_forward(
                mesh, lambda x, p: tfm.apply_layers(p, x, 2, jnp.arange(4)),
                n_microbatches=3,
            )(stack, xs)


class TestLMTrainStep:
    def test_dp_sp_ep_step_decreases_loss(self):
        mesh = make_mesh(8, axes=("dp", "sp", "ep"), shape=(2, 2, 2))
        params = lm.init_lm_params(
            jax.random.PRNGKey(0), vocab=64, d_model=32, n_heads=4,
            n_layers=2, n_experts=4,
        )
        step, params = lm.make_lm_train_step(mesh, params, n_heads=4, ep_axis="ep")
        toks = jnp.asarray(
            np.random.default_rng(8).integers(0, 64, (4, 17)), jnp.int32
        )
        params, loss1 = step(params, toks)
        params, loss2 = step(params, toks)
        assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)

    def test_sequence_parallel_forward_matches_dense(self):
        mesh = make_mesh(8, axes=("dp", "sp", "ep"), shape=(2, 2, 2))
        params = lm.init_lm_params(
            jax.random.PRNGKey(1), vocab=64, d_model=32, n_heads=4, n_layers=2
        )
        attn = lm._make_attn_fn(mesh, "ring", "dp", "sp")
        x = jnp.asarray(np.random.default_rng(9).integers(0, 64, (4, 16)), jnp.int32)
        dense = tfm.apply(params, x, 4)
        ring = jax.jit(lambda t: tfm.apply(params, t, 4, attn_fn=attn))(x)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-4)

    def test_kv_chunked_train_step_matches_unchunked(self):
        """kv_chunk is a memory knob, not a numerics knob: the sequence-
        parallel forward with chunked in-shard attention equals dense."""
        mesh = make_mesh(8, axes=("dp", "sp", "ep"), shape=(2, 2, 2))
        params = lm.init_lm_params(
            jax.random.PRNGKey(3), vocab=64, d_model=32, n_heads=4, n_layers=2
        )
        attn = lm._make_attn_fn(mesh, "ring", "dp", "sp", kv_chunk=4)
        x = jnp.asarray(
            np.random.default_rng(11).integers(0, 64, (4, 16)), jnp.int32
        )
        dense = tfm.apply(params, x, 4)
        ring = jax.jit(lambda t: tfm.apply(params, t, 4, attn_fn=attn))(x)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-4)
        step, sparams = lm.make_lm_train_step(
            mesh, params, n_heads=4, kv_chunk=4
        )
        toks = jnp.asarray(
            np.random.default_rng(12).integers(0, 64, (4, 17)), jnp.int32
        )
        _, loss = step(sparams, toks)
        assert np.isfinite(float(loss))

    def test_ulysses_attn_kind(self):
        mesh = make_mesh(8, axes=("dp", "sp"), shape=(2, 4))
        params = lm.init_lm_params(
            jax.random.PRNGKey(2), vocab=32, d_model=32, n_heads=4, n_layers=1
        )
        step, params = lm.make_lm_train_step(mesh, params, n_heads=4, attn="ulysses")
        toks = jnp.asarray(np.random.default_rng(10).integers(0, 32, (2, 17)), jnp.int32)
        _, loss = step(params, toks)
        assert np.isfinite(float(loss))


def test_zoo_transformer_lm():
    from nnstreamer_tpu.models import zoo

    m = zoo.get("transformer_lm", vocab="64", d_model="32", n_heads="4",
                n_layers="1", seqlen="8")
    out = jax.eval_shape(
        m.fn, jax.ShapeDtypeStruct((1, 8), jnp.int32)
    )
    assert out.shape == (1, 8, 64)
