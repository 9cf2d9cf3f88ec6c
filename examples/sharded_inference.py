"""Multi-chip inference from the filter surface: mesh-sharded filters and
the fused face cascade.

Run on any host (the virtual CPU mesh stands in for a TPU slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/sharded_inference.py

- ``custom="mesh:dp2tp4"`` pjits one tensor_filter over a 2x4 device mesh:
  batch shards over dp, weights column-parallel over tp, XLA GSPMD inserts
  the collectives (reference analogue: the accelerator-selection machinery
  of tensor_filter_common.c:451-, where the "accelerator" here is a slice).
- ``zoo:face_composite`` runs detect→crop+resize→landmark as ONE XLA
  program (the reference's tensor_crop cascade without the host hop).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

import numpy as np  # noqa: E402

from nnstreamer_tpu.single import SingleShot  # noqa: E402


def main() -> None:
    rng = np.random.default_rng(0)

    # -- TP/DP-sharded ViT classifier, one property away
    batch = 8
    with SingleShot(
        framework="jax",
        model="zoo:vit",
        custom=f"batch:{batch},size:64,patch:16,d_model:128,n_heads:4,"
               "n_layers:2,num_classes:10,mesh:dp2tp4",
    ) as s:
        imgs = rng.integers(0, 255, (batch, 64, 64, 3), np.uint8)
        (logits,) = s.invoke(imgs)
        print(f"sharded vit logits: {np.asarray(logits).shape} "
              f"(mesh dp2tp4 over {len(jax.devices())} devices)")

    # -- fused face cascade: one program, no host hop at the crop
    with SingleShot(
        framework="jax", model="zoo:face_composite", custom="threshold:0.25"
    ) as s:
        frame = rng.integers(0, 255, (1, 128, 128, 3), np.uint8)
        landmarks, detections = s.invoke(frame)
        det = np.asarray(detections)
        n = int((det[:, 2] >= 0.25).sum())
        print(f"fused cascade: {n} faces above threshold, "
              f"landmarks {np.asarray(landmarks).shape}")


if __name__ == "__main__":
    main()
