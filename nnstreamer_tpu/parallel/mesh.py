"""Device mesh helpers: the substrate of the multi-chip layer.

The reference scales across devices by sharding *pipelines* over host
networking (tensor_query/edge, SURVEY.md §2.6). TPU-native scaling instead
starts from a jax.sharding.Mesh over ICI: single filters shard via jit
shardings (TP/DP), pipeline stages place on device subsets, and collectives
ride ICI instead of TCP. These helpers build meshes that work identically on
real chips and on the virtual CPU mesh used in tests.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    n_devices: Optional[int] = None,
    axes: Sequence[str] = ("dp", "tp"),
    shape: Optional[Sequence[int]] = None,
    devices=None,
) -> Mesh:
    """Build a Mesh over the first n devices.

    Default factoring puts as much as possible on the *last* axis (model/tp —
    contiguous devices share fastest ICI links) and the remainder on the
    first (data). shape=None with axes=("dp","tp") on 8 devices → (2, 4).
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    n = len(devs)
    if shape is None:
        if len(axes) == 1:
            shape = (n,)
        elif len(axes) == 2:
            # largest power-of-two split favoring the last axis
            tp = 1
            while tp * 2 <= n and (n % (tp * 2)) == 0 and tp * 2 <= 4:
                tp *= 2
            shape = (n // tp, tp)
        else:
            shape = (n,) + (1,) * (len(axes) - 1)
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, tuple(axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))


def channel_sharding(mesh: Mesh, ndim: int, axis: str = "tp") -> NamedSharding:
    """Shard the trailing (channel/feature) dim — NHWC/HWIO tensors."""
    return NamedSharding(mesh, P(*([None] * (ndim - 1) + [axis])))
