"""One publisher, two subscribers via the vendored MQTT broker.

Launch-string equivalents (pre-flight with ``nns-launch --check``):

    videotestsrc num-frames=4 ! tensor_converter ! mqttsink pub-topic=demo/video
    mqttsrc sub-topic=demo/video ! tensor_sink
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import time

from nnstreamer_tpu.edge.mqtt import MqttBroker
from nnstreamer_tpu.edge.mqtt_elems import MqttSink, MqttSrc
from nnstreamer_tpu.elements.converter import TensorConverter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.sources import VideoTestSrc
from nnstreamer_tpu.pipeline.graph import Pipeline

broker = MqttBroker()
print(f"broker on port {broker.port}")

subs = []
for i in range(2):
    sink = TensorSink()
    p = Pipeline().chain(
        MqttSrc(port=broker.port, **{"sub-topic": "demo/#"}), sink)
    subs.append((p, p.start(), sink))
time.sleep(0.3)

Pipeline().chain(
    VideoTestSrc(width=16, height=16, **{"num-frames": 5}),
    TensorConverter(),
    MqttSink(port=broker.port, **{"pub-topic": "demo/cam0"}),
).run(timeout=60)

for i, (p, ex, sink) in enumerate(subs):
    ex.wait(timeout=30)
    p.stop()
    print(f"subscriber {i}: received {sink.rendered} frames")
broker.close()
