"""Regression tests for review findings: timeouts, re-run guard, flexbuf
roundtrip, audio batching, appsrc shutdown, decoder un-batching."""

import numpy as np
import pytest

from nnstreamer_tpu.elements.converter import TensorConverter
from nnstreamer_tpu.elements.decoder import TensorDecoder
from nnstreamer_tpu.elements.sink import FakeSink, TensorSink
from nnstreamer_tpu.elements.sources import AppSrc, AudioTestSrc, TensorSrc, VideoTestSrc
from nnstreamer_tpu.pipeline.graph import Pipeline
from nnstreamer_tpu.pipeline.parse import parse_pipeline
from nnstreamer_tpu.tensors.spec import TensorsSpec


def test_run_timeout_raises():
    src = VideoTestSrc(width=8, height=8, **{"num-frames": -1})
    p = Pipeline().chain(src, TensorConverter(), FakeSink())
    with pytest.raises(TimeoutError):
        p.run(timeout=0.3)


def test_rerun_completed_pipeline_raises():
    p = Pipeline().chain(TensorSrc(dimensions="2", **{"num-frames": 1}), TensorSink())
    p.run(timeout=30)
    with pytest.raises(RuntimeError, match="already ran"):
        p.run(timeout=30)


def test_appsrc_stop_without_eos_does_not_hang():
    src = AppSrc(spec=TensorsSpec.from_strings("2", "float32"))
    sink = TensorSink()
    p = Pipeline().chain(src, sink)
    p.start()
    src.push(np.zeros(2, np.float32))
    import time

    deadline = time.monotonic() + 10
    while sink.rendered < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    p.stop()  # no end_of_stream() sent; must not hang
    assert sink.rendered == 1


def test_flexbuf_roundtrip_through_pipeline(tmp_path):
    # encode: tensors → flexbuf bytes file
    p1 = parse_pipeline(
        f"tensorsrc dimensions=3:2 types=float32 num-frames=1 pattern=ones ! "
        f"tensor_decoder mode=flexbuf ! filesink location={tmp_path}/f.flex"
    )
    p1.run(timeout=30)
    # decode: flexbuf bytes → tensors
    p2 = parse_pipeline(
        f"filesrc location={tmp_path}/f.flex ! tensor_converter mode=flexbuf ! "
        f"tensor_sink name=out"
    )
    p2.run(timeout=30)
    out = p2["out"].frames[0]
    assert out.tensors[0].shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(out.tensors[0]), 1.0)


def test_audio_frames_per_tensor_batches():
    src = AudioTestSrc(**{"num-buffers": 4, "samples-per-buffer": 100})
    conv = TensorConverter(**{"frames-per-tensor": 2})
    sink = TensorSink()
    Pipeline().chain(src, conv, sink).run(timeout=30)
    assert sink.rendered == 2
    assert sink.frames[0].tensors[0].shape == (200, 1)


def test_direct_video_unbatches():
    src = VideoTestSrc(width=8, height=8, **{"num-frames": 4})
    conv = TensorConverter(**{"frames-per-tensor": 2})
    dec = TensorDecoder(mode="direct_video")
    sink = TensorSink()
    Pipeline().chain(src, conv, dec, sink).run(timeout=30)
    assert sink.rendered == 4  # 2 batched tensors → 4 media frames
    assert sink.frames[0].tensors[0].shape == (8, 8, 3)


def test_combination_empty_token_clean_error():
    from nnstreamer_tpu.elements.filter import _parse_combination

    with pytest.raises(ValueError, match="empty token"):
        _parse_combination("o0,,i1")


def test_deterministic_element_names():
    from nnstreamer_tpu.elements.flow import Queue

    a, b = Queue(), Queue()
    assert a.name != b.name
    assert a.name.startswith("queue")


@pytest.mark.parametrize("requested", [None, "tpu"])
def test_entry_points_leave_jax_platforms_alone(requested):
    """No entry point second-guesses the platform: with JAX_PLATFORMS
    unset or naming the TPU, importing every CLI leaves the variable and
    jax's own config exactly as the caller set them — a missing chip is
    jax's error to raise, never a quiet switch to the CPU."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if requested is not None:
        env["JAX_PLATFORMS"] = requested
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import os, jax\n"
        "import nnstreamer_tpu.cli, nnstreamer_tpu.analysis.cli\n"
        "import nnstreamer_tpu.analysis.kscope_cli\n"
        "import nnstreamer_tpu.analysis.san_cli\n"
        "import nnstreamer_tpu.analysis.xray_cli\n"
        "print(repr(os.environ.get('JAX_PLATFORMS')),"
        " repr(jax.config.jax_platforms))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout.split() == [repr(requested), repr(requested)]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """chip_smoke.py on the CPU: non-zero exit and no ``"ok"`` line —
    the chip check can never pass on a machine without the chip."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_device_crop_clips_to_dtype_range():
    """Integer crop outputs clip to the DTYPE's range, not 0..255 —
    0..255 would wrap int8 on astype and clamp valid uint16 values
    (ADVICE r3)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.elements.control import TensorCrop
    from nnstreamer_tpu.tensors.spec import TensorsSpec

    for dt, lo, hi in (("int8", -128, 127), ("uint16", 0, 65535)):
        crop = TensorCrop(**{"out-size": "2:2", "max-crops": 1})
        crop.negotiate(
            [
                TensorsSpec.from_strings("3:8:8:1", dt),
                TensorsSpec.from_strings("4:1", "uint32"),
            ]
        )
        # a bright uint16 image must survive >255; a negative int8 image
        # must keep its sign (the old clip(0,255) floor zeroed it)
        fill = 300.0 if dt == "uint16" else -100.0
        img = jnp.full((1, 8, 8, 3), fill, dt)
        boxes = jnp.asarray([[0, 0, 4, 4]], jnp.float32)
        crops, _ = crop._jit_crop(img, boxes)
        assert crops.dtype == np.dtype(dt)
        vals = np.asarray(crops)
        if dt == "uint16":
            assert vals.max() == 300  # preserved, not clamped to 255
        else:
            assert vals.min() == -100  # preserved, not floored at 0


def test_ngram_lookup_distinguishes_no_match():
    """ngram_lookup returns None (not zeros) when the context tail has
    no earlier occurrence — spec_step uses this to skip wasted verify
    columns (ADVICE r3)."""
    from nnstreamer_tpu.models.speculative import ngram_lookup, ngram_propose

    ctx = np.asarray([5, 6, 7, 8], np.int32)  # tail [8] appears once only
    assert ngram_lookup(ctx, 3, 1) is None
    assert list(ngram_propose(ctx, 3, 1)) == [0, 0, 0]  # padded form
    rep = np.asarray([1, 2, 9, 1, 2], np.int32)  # tail [2] seen earlier
    got = ngram_lookup(rep, 2, 1)
    assert got is not None and list(got) == [9, 1]


def test_spec_context_includes_prefix_tokens():
    """submit(prefix=id) requests carry the PREFIX tokens in their
    spec_step proposal context (ADVICE r3: n-gram matches often live in
    the shared system prompt)."""
    import jax

    from nnstreamer_tpu.models import transformer as tfm
    from nnstreamer_tpu.models.serving import ContinuousBatcher

    params = tfm.init_params(
        jax.random.PRNGKey(0), vocab=64, d_model=32, n_heads=2, n_layers=1
    )
    cb = ContinuousBatcher(params, 2, n_slots=1, max_len=64, prompt_len=8)
    pfx_toks = np.asarray([3, 4, 5, 6, 7, 9, 11, 13], np.int32)
    pid = cb.register_prefix(pfx_toks)
    rid = cb.submit(np.asarray([1, 2], np.int32), 2, prefix=pid)
    (req,) = [r for r in cb._slots if r is not None] or [
        p.req for p in cb._pending
    ]
    assert list(req.prompt[: len(pfx_toks)]) == list(pfx_toks)
    while cb.result(rid) is None:
        cb.spec_step(k=3)


def test_chan_2deep_lockstep_stays_under_one_beat():
    """Regression (_Chan wake discipline): a 2-deep channel in strict
    producer/consumer lockstep must never eat a 50 ms wait beat — the
    consumer draining to the low-water mark between the producer's
    checks has to wake it (the Dekker advertise-then-recheck pairing).
    32 items through a full channel finish in well under one beat."""
    import threading
    import time

    from nnstreamer_tpu.pipeline.executor import _Chan

    stop = threading.Event()
    ch = _Chan(2)
    n = 32
    got = []

    def consume():
        while len(got) < n:
            got.append(ch.get(stop))

    t = threading.Thread(target=consume, daemon=True)
    t0 = time.perf_counter()
    t.start()
    for i in range(n):
        ch.put(i, stop)
    t.join(timeout=5)
    elapsed = time.perf_counter() - t0
    assert got == list(range(n))
    # a genuinely missed wake costs a 50 ms beat per parked put (~1.5 s
    # for 32 items through a 2-deep channel); the bound discriminates
    # that while absorbing loaded-runner scheduling noise
    assert elapsed < 0.5, f"missed wake: {elapsed*1000:.1f} ms for {n} items"


def test_chan_drain_wakes_parked_producer():
    """Regression (batch-collector interaction): drain() stops above the
    low-water mark and the consumer then computes for a whole batch — a
    parked producer must still be woken the moment space frees, not
    sleep out its 50 ms beat."""
    import threading
    import time

    from nnstreamer_tpu.pipeline.executor import _Chan

    stop = threading.Event()

    def attempt() -> float:
        ch = _Chan(8)
        for i in range(8):
            ch.put(i, stop)  # fill: next put parks
        put_done = threading.Event()

        def producer():
            ch.put(8, stop)
            put_done.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        time.sleep(0.01)  # let the producer park
        t0 = time.perf_counter()
        items = ch.drain(2)  # 8→6: above low-water (4), space freed
        assert items == [0, 1]
        assert put_done.wait(timeout=1.0)
        woke_ms = (time.perf_counter() - t0) * 1000
        t.join(timeout=1)
        return woke_ms

    # min-of-3: a missed wake is deterministic (every attempt sleeps the
    # full 50 ms beat), while scheduler noise on a loaded runner is not
    best = min(attempt() for _ in range(3))
    assert best < 40, f"producer slept a full beat: {best:.1f} ms"
