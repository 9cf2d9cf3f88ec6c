"""The program's own account of its set-up: the ``nns_llm_setup_seconds``
gauges, read from the registry the driver enabled (it outlives the pipeline).
A program without them gives None: the metric is left out of the line."""


def read(phase: str):
    from nnstreamer_tpu.obs import metrics

    reg = metrics.get()
    gauge = reg.find("nns_llm_setup_seconds", phase=phase) if reg else None
    return None if gauge is None else float(gauge.value)
