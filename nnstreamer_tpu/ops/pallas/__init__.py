"""Hand-written Pallas TPU kernels for the hot ops.

XLA fuses most of the pipeline (SURVEY.md §7 design mapping); these kernels
cover the cases where explicit VMEM blocking beats the fusion XLA picks —
flash attention, the serving decode kernels (contiguous and paged cache
layouts, the latent cache, Kimi Delta Attention's and Mamba-2's recurrent
state, the routed experts some token picked), and the
pre/post-processing set (docs/on-device-ops.md):
MXU bilinear crop/resize with a fused normalize epilogue, and the greedy
NMS suppression recurrence. Every kernel has an ``interpret=True`` path so
the CPU test mesh exercises the same code the TPU runs.

Importing this package registers every kernel's :class:`KernelSpec` with
:mod:`~nnstreamer_tpu.ops.pallas.registry` (the nns-kscope substrate:
grid/BlockSpec geometry, dtype support, jnp reference, shape grid).
"""

from nnstreamer_tpu.ops.pallas.decode_attention import (  # noqa: F401
    decode_attention,
)
from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention  # noqa: F401
from nnstreamer_tpu.ops.pallas.image_kernels import (  # noqa: F401
    crop_and_resize,
    resize_bilinear,
)
from nnstreamer_tpu.ops.pallas.held_experts import held_experts_ffn  # noqa: F401
from nnstreamer_tpu.ops.pallas.kda import kda_decode_step  # noqa: F401
from nnstreamer_tpu.ops.pallas.mla_attention import (  # noqa: F401
    mla_paged_decode_attention,
)
from nnstreamer_tpu.ops.pallas.nms import nms  # noqa: F401
from nnstreamer_tpu.ops.pallas.paged_attention import (  # noqa: F401
    paged_decode_attention,
)
from nnstreamer_tpu.ops.pallas.ssm import ssm_decode_step  # noqa: F401
