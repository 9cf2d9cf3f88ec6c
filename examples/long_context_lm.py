"""Ring-attention LM training step on a virtual mesh (run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
on CPU; on a TPU slice the same code spans real chips)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.parallel import lm
from nnstreamer_tpu.parallel.mesh import make_mesh

n = len(jax.devices())
shape = (2, 2, 2) if n == 8 else (n, 1, 1)
mesh = make_mesh(axes=("dp", "sp", "ep"), shape=shape)
print("mesh:", dict(mesh.shape))
params = lm.init_lm_params(jax.random.PRNGKey(0), vocab=256, d_model=128,
                           n_heads=8, n_layers=4, n_experts=4)
step, params = lm.make_lm_train_step(
    mesh, params, n_heads=8,
    ep_axis="ep" if "ep" in mesh.shape else None)
b = 2 * mesh.shape["dp"]  # batch shards over dp
toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (b, 129)),
                   jnp.int32)
for i in range(5):
    params, loss = step(params, toks)
    print(f"step {i}: loss {float(loss):.4f}")
