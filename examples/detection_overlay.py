"""SSD detection with on-device NMS decoded to an RGBA overlay
(the reference's nnstreamer_decoder_boundingbox example pipeline).

Launch-string equivalent (pre-flight it with ``nns-launch --check``):

    videotestsrc width=300 height=300 num-frames=4 ! tensor_converter !
        tensor_filter framework=jax model=zoo:ssd_mobilenet_v2_pp custom=threshold:0.0001 !
        tensor_decoder mode=bounding_boxes option1=mobilenet-ssd-postprocess option4=300:300 !
        tensor_sink
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

src = VideoTestSrc(width=300, height=300, **{"num-frames": 4})
filt = TensorFilter(framework="jax", model="zoo:ssd_mobilenet_v2_pp",
                    custom="threshold:0.0001")
dec = TensorDecoder(mode="bounding_boxes",
                    option1="mobilenet-ssd-postprocess", option4="300:300")
sink = TensorSink()
Pipeline().chain(src, TensorConverter(), filt, dec, sink).run(timeout=300)
for i, f in enumerate(sink.frames):
    dets = f.meta["detections"]
    print(f"frame {i}: {dets.shape[0]} detections, overlay "
          f"{f.tensors[0].shape}")
