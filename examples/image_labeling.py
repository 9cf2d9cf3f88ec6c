"""The v0 end-to-end slice (SURVEY.md §7 build order 2): deterministic
frames → fused normalize+MobileNet-v2 → argmax class indices.

Launch-string equivalent (pre-flight it with ``nns-launch --check``):

    videotestsrc width=224 height=224 num-frames=8 ! tensor_converter !
        tensor_filter framework=jax model=zoo:mobilenet_v2 !
        tensor_decoder mode=image_labeling ! tensor_sink
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from nnstreamer_tpu.elements.converter import TensorConverter
from nnstreamer_tpu.elements.decoder import TensorDecoder
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.sources import VideoTestSrc
from nnstreamer_tpu.pipeline.graph import Pipeline

src = VideoTestSrc(width=224, height=224, **{"num-frames": 8})
filt = TensorFilter(framework="jax", model="zoo:mobilenet_v2")
dec = TensorDecoder(mode="image_labeling")
sink = TensorSink()
Pipeline().chain(src, TensorConverter(), filt, dec, sink).run(timeout=300)
for i, f in enumerate(sink.frames):
    print(f"frame {i}: class {int(np.asarray(f.tensors[0])[0])}")
