"""Pipeline split across two processes: a TPU-side server pipeline serves a
client pipeline over the native TCP transport (reference edge-ai offload).

Launch-string equivalents (pre-flight with ``nns-launch --check``):

    tensor_query_serversrc port=5001 max-clients=4 max-inflight=16 !
        tensor_filter framework=jax model=zoo:add custom=dims:4,const:10 input=4 inputtype=float32 !
        tensor_query_serversink
    tensorsrc dimensions=4 num-frames=8 ! tensor_query_client dest-port=5001 ! tensor_sink

The server carries admission bounds (docs/edge-serving.md) — a query
server without any is the overload-collapse topology nns-lint flags as
NNS-W111.

Distributed tracing (docs/observability.md): run with NNS_TRACE_DIR=/tmp/t
and both processes record chrome traces — the client stamps each request
with a frame_id that rides the wire meta, so ``trace.merge()`` folds
client.json + server.json into ONE merged.json timeline where the client
span sits over the server-side work it caused (load it in Perfetto).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import multiprocessing as mp

TRACE_DIR = os.environ.get("NNS_TRACE_DIR")


def server(port_q, stop_q):
    from nnstreamer_tpu.edge.query import TensorQueryServerSrc, TensorQueryServerSink
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.pipeline.graph import Pipeline

    tracer = None
    if TRACE_DIR:
        from nnstreamer_tpu import trace as trace_mod

        tracer = trace_mod.enable()
        tracer.set_process("query-server")
    src = TensorQueryServerSrc(port=0, **{"max-clients": 4,
                                          "max-inflight": 16})
    # serversrc emits format=flexible; declare the static input spec
    filt = TensorFilter(framework="jax", model="zoo:add", custom="dims:4,const:10",
                        input="4", inputtype="float32")
    sink = TensorQueryServerSink()
    p = Pipeline().chain(src, filt, sink)
    ex = p.start()
    port_q.put(src.bound_port)
    stop_q.get()  # serve until the parent says stop
    ex.stop()
    if tracer is not None:
        tracer.save(os.path.join(TRACE_DIR, "server.json"))


if __name__ == "__main__":
    import numpy as np

    from nnstreamer_tpu.edge.query import TensorQueryClient
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import TensorSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline

    tracer = None
    if TRACE_DIR:
        from nnstreamer_tpu import trace as trace_mod

        os.makedirs(TRACE_DIR, exist_ok=True)
        tracer = trace_mod.enable()
        tracer.set_process("query-client")
    q = mp.Queue()
    stop_q = mp.Queue()
    proc = mp.Process(target=server, args=(q, stop_q), daemon=True)
    proc.start()
    port = q.get(timeout=30)

    src = TensorSrc(dimensions="4", types="float32", **{"num-frames": 3})
    client = TensorQueryClient(**{"dest-port": port})
    sink = TensorSink()
    Pipeline().chain(src, client, sink).run(timeout=60)
    for i, f in enumerate(sink.frames):
        print(f"reply {i}: {np.asarray(f.tensors[0])} "
              f"(frame_id={f.meta.get('frame_id')})")
    stop_q.put(None)  # let the server save its trace and exit cleanly
    proc.join(timeout=30)
    if tracer is not None:
        import json

        from nnstreamer_tpu import trace as trace_mod

        client_path = os.path.join(TRACE_DIR, "client.json")
        tracer.save(client_path)
        server_path = os.path.join(TRACE_DIR, "server.json")
        if os.path.exists(server_path):
            with open(client_path) as f1, open(server_path) as f2:
                merged = trace_mod.merge([json.load(f1), json.load(f2)])
            merged_path = os.path.join(TRACE_DIR, "merged.json")
            with open(merged_path, "w") as f:
                json.dump(merged, f)
            print(f"merged chrome trace: {merged_path} (open in Perfetto)")
        else:
            # server died or hung before saving: keep the client half
            print(f"server trace missing; client trace at {client_path}")
    if proc.is_alive():
        proc.terminate()
