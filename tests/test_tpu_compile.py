"""The TPU compiler's verdict on the main-path programs, without a chip.

libtpu is installed and compiles for a chip that is *described*, not
attached (``jax.experimental.topologies``), so what Mosaic and XLA:TPU
refuse — a block off the (8, 128) tiling, an unaligned dynamic slice, a
kernel over its VMEM budget — fails here, on every later PR, at no chip
time. Every Pallas kernel in ``ops/pallas/`` had passed its
interpret-mode parity tests for nineteen PRs while the real compiler
refused four of the five at every shape.

Shapes: the width ``chip_smoke.py`` serves (16 heads of 128) and the zoo
default (8 heads of 32); NMS at SSD's 1,917 anchors; crop-and-resize and
resize at camera frames; plus the flagship MobileNet-v2 224 forward. A
compile that passes here is not a chip run and is never reported as one.

One file, on purpose: only one process at a time may load libtpu, and
the worker that runs this file keeps it until it exits. The topology is
described inside a module-scoped fixture — never at import, in a
``skipif`` or in ``parametrize`` arguments — so every xdist worker
collects the same tests and only the one that is handed this file loads
the library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The described chip, with the persistent compile cache off around
    this file's compiles: a TPU entry written here could not be read
    back without a chip, and the next run would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; ``shapes`` are
    ``(shape, dtype)`` pairs. Returns the compiled text."""
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


#: (heads, head dim): what chip_smoke.py serves, and the zoo default
WIDTHS = [(16, 128), (8, 32)]
f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32


@pytest.mark.parametrize("h,d", WIDTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(one_chip, h, d, dtype):
    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention

    q = ((2, 512, h, d), dtype)
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention(q, k, v), one_chip, q, q, q
    ))


@pytest.mark.parametrize("h,d", WIDTHS)
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_decode_attention(one_chip, h, d, quantized):
    from nnstreamer_tpu.ops.pallas.decode_attention import decode_attention

    b, s_len = 4, 2048
    q, pos = ((b, 1, h, d), f32), ((b,), i32)
    if quantized:
        cache, scale = ((b, s_len, h, d), i8), ((b, s_len, h), f32)
        text = _compile(
            lambda q, k, v, p, ks, vs: decode_attention(
                q, k, v, p, k_scale=ks, v_scale=vs
            ),
            one_chip, q, cache, cache, pos, scale, scale,
        )
    else:
        cache = ((b, s_len, h, d), f32)
        text = _compile(
            lambda q, k, v, p: decode_attention(q, k, v, p),
            one_chip, q, cache, cache, pos,
        )
    _assert_kernel(text)


@pytest.mark.parametrize("h,d", WIDTHS)
@pytest.mark.parametrize("variant", ["fp", "gqa", "int8"])
def test_paged_attention(one_chip, h, d, variant):
    from nnstreamer_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    b, n_blocks, bs, nb = 4, 64, 16, 8
    kv = h // 4 if variant == "gqa" else h  # 16q/4kv at the smoke width
    q, fresh = ((b, 1, h, d), f32), ((b, 1, kv, d), f32)
    tables, pos = ((b, nb), i32), ((b,), i32)
    if variant == "int8":
        arena = ((n_blocks, bs, kv, d), i8)
        scale = ((n_blocks, bs, kv), f32)
        text = _compile(
            lambda q, k, v, t, p, fk, fv, ks, vs: paged_decode_attention(
                q, k, v, t, p, fk, fv, k_scale=ks, v_scale=vs
            ),
            one_chip, q, arena, arena, tables, pos, fresh, fresh,
            scale, scale,
        )
    else:
        arena = ((n_blocks, bs, kv, d), f32)
        text = _compile(
            lambda q, k, v, t, p, fk, fv: paged_decode_attention(
                q, k, v, t, p, fk, fv
            ),
            one_chip, q, arena, arena, tables, pos, fresh, fresh,
        )
    _assert_kernel(text)


#: the benchmark's cells (BENCHMARK.json): slots, heads, KV heads,
#: layers, arena blocks, model width, FFN width, vocabulary; 64 table
#: entries of 16 tokens, head dim 128, float32
CELLS = {
    "olmo-1b": (16, 16, 16, 16, 1025, 2048, 8192, 50304),
    "mistral-7b": (32, 32, 8, 8, 2049, 4096, 14336, 32000),
}
NB, BS, HD = 64, 16, 128


def _big_moves(text, floor, tail="", ops="copy|copy-start|dynamic-slice",
               dtype=r"\w+"):
    """Result shapes of every ``copy`` / ``dynamic-slice`` (and their
    async starts; or the ``ops`` given) in a compiled text, fused
    computations' instructions included, whose dims end with ``tail``
    (an arena's: block size, KV heads, head dim), whose element type is
    ``dtype`` and which hold at least ``floor`` elements."""
    found = []
    for m in re.finditer(
        rf"= \(?{dtype}\[([\d,]+)\][^=\n]*? ({ops})\(", text,
    ):
        n = math.prod(int(x) for x in m.group(1).split(","))
        if n >= floor and m.group(1).endswith(tail):
            found.append((m.group(2), m.group(1)))
    return found


@pytest.mark.parametrize("cell", list(CELLS))
def test_paged_attention_cell_shapes(one_chip, cell):
    """The kernel as the cells launch it: the arena leaves whole, the
    layer a traced scalar."""
    from nnstreamer_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    b, h, kv, layers, n, *_ = CELLS[cell]
    arena = ((layers, n, BS, kv, HD), f32)
    q, fresh = ((b, 1, h, HD), f32), ((b, 1, kv, HD), f32)
    text = _compile(
        lambda q, k, v, t, p, fk, fv, li: paged_decode_attention(
            q, k, v, t, p, fk, fv, layer=li
        ),
        one_chip, q, arena, arena, ((b, NB), i32), ((b,), i32), fresh,
        fresh, ((), i32),
    )
    _assert_kernel(text)
    assert not _big_moves(text, n * BS * kv * HD, f",{BS},{kv},{HD}")


@pytest.mark.parametrize("cell", list(CELLS))
def test_paged_decode_step_cell_shapes(one_chip, cell):
    """The decode program that is served — the batcher's own pump builder
    over the paged layout, two steps, arena and history donated: the
    kernel is in it, and nothing the size of ONE layer's arena leaf
    (134 MB) is copied or sliced anywhere — the layer scan feeds the
    kernel the whole leaf and an index."""
    from nnstreamer_tpu.models import transformer as tfm
    from nnstreamer_tpu.models.family import DenseFamily
    from nnstreamer_tpu.models.serving import _PagedLayout, make_pump
    from nnstreamer_tpu.ops.pallas.paged_attention import (
        make_paged_attention,
    )

    b, h, kv, layers, n, d_model, d_ff, vocab = CELLS[cell]
    params = jax.eval_shape(
        lambda: tfm.init_params(
            jax.random.PRNGKey(0), vocab, d_model, h, layers, d_ff=d_ff,
            n_kv_heads=kv,
        )
    )
    family = DenseFamily(params, h, 512, f32)
    pump = make_pump(
        _PagedLayout(family, make_paged_attention(interpret=False)), False
    )
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    arena = sds((layers, n, BS, kv, HD), f32)
    vec, fvec = sds((b,), i32), sds((b,), f32)
    text = pump.lower(
        (jax.tree.map(lambda x: sds(x.shape, x.dtype), params), None),
        vec, vec, sds((b,), jnp.bool_), (arena, arena),  # tok pos active
        sds((b, NB * BS), i32), vec, vec,                # hist budget stop
        fvec, vec, fvec, sds((b, 2), jnp.uint32),        # the sampler's
        sds((b, NB), i32),                               # tables
        n_steps=2,
    ).compile().as_text()
    assert "jit_impl" in text  # the name benchmark/configs select it by
    _assert_kernel(text)
    assert not _big_moves(text, n * BS * kv * HD, f",{BS},{kv},{HD}")
    # arena (both leaves) and history are donated and come back in place
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert aliased.count("may-alias") + aliased.count("must-alias") == 3


@pytest.mark.parametrize("heads", [8, 16, 32])
def test_kda_decode_step_cell_shape(one_chip, heads):
    """Kimi Delta Attention's decode recurrence as kimi-linear-48b-a3b's
    cell launches it: 128 lanes, 32 heads of 128 x 128 float32 state, 7 KDA
    layers in one leaf, the leaf donated and updated in place."""
    from nnstreamer_tpu.ops.pallas.kda import kda_decode_step

    b, h, d, layers = 128, 32, 128, 7
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((layers, b + 1, h, d, d), f32), ((b, h, d), f32), ((b, h, d), f32),
        ((b, h, d), f32), ((b, h, d), f32), ((b, h), f32), ((b,), jnp.bool_))]
    text = jax.jit(
        lambda s, q, k, v, a, be, act: kda_decode_step(
            s, q, k, v, a, be, act, layer=3, heads=heads, interpret=False),
        donate_argnums=0,
    ).lower(*args).compile().as_text()
    _assert_kernel(text)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert "alias" in aliased   # the state comes back in its own buffer
    assert not _big_moves(text, b * h * d * d, f",{h},{d},{d}")


#: (tokens, d_model, expert width, held experts) of a decode step's expert layer
EXPERT_CELLS = {"longcat-flash-chat": (64, 6144, 2048, 16),
                "kimi-linear-48b-a3b": (128, 2304, 1024, 64)}


@pytest.mark.parametrize("cell", list(EXPERT_CELLS))
def test_held_experts_ffn_cell_shapes(one_chip, cell):
    """The decode step's expert kernel as the two cells launch it: every
    held expert's bfloat16 weights whole (the kernel reads the hit ones
    through its index maps), a step's tokens, float32 gates. Nothing the
    size of one expert's projection is copied or sliced beside it."""
    from nnstreamer_tpu.ops.pallas.held_experts import held_experts_ffn

    t, d, f, n = EXPERT_CELLS[cell]
    bf16 = jnp.bfloat16
    text = _compile(
        lambda x, g, wg, wu, wd: held_experts_ffn(x, g, wg, wu, wd, interpret=False),
        one_chip, ((t, d), bf16), ((t, n), f32), ((n, d, f), bf16),
        ((n, d, f), bf16), ((n, f, d), bf16),
    )
    _assert_kernel(text)
    assert "held_experts_ffn" in text   # the name the roofline's reader finds
    assert not _big_moves(text, d * f)


def _step_sized_moves(text, floor):
    """Every float32 ``copy`` / ``transpose`` of at least ``floor`` elements:
    a step's vectors are float32; the weights a program also moves are not."""
    return _big_moves(text, floor, ops="copy|copy-start|transpose", dtype="f32")


@pytest.mark.parametrize("heads", [8, 32, 64])
def test_ssm_decode_step_cell_shape(one_chip, heads):
    """Mamba-2's decode scan as granite-4.0-h-small's cell launches it: 64
    lanes, 128 heads of 64 x 128 float32 state two to a row of the leaf
    ([9, 65, 64, 128, 128]), 9 SSM layers in one leaf, the leaf donated and
    updated in place; the step's vectors ([B, H x P], as the projection leaves
    them) reach the kernel's rows and its rows the output by reshapes: nothing
    their size is transposed beside the kernel."""
    from nnstreamer_tpu.ops.pallas.ssm import heads_per_row, ssm_decode_step

    b, h, p, n, layers = 64, 128, 64, 128, 9
    k = heads_per_row(h, p)
    assert k == 2
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((layers, b + 1, h // k, n, k * p), f32), ((b, h * p), f32), ((b, n), f32),
        ((b, n), f32), ((b, h), f32), ((b, h), f32), ((h,), f32),
        ((b,), jnp.bool_))]

    def step(s, x, bm, cm, dt, a, d, act):
        s, y = ssm_decode_step(s, x.reshape(b, h, p), bm, cm, dt, a, d, act,
                               layer=4, heads=heads, interpret=False)
        return s, y.reshape(b, h * p)

    text = jax.jit(step, donate_argnums=0).lower(*args).compile().as_text()
    _assert_kernel(text)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert "alias" in aliased   # the state comes back in its own buffer
    assert not _big_moves(text, b * n * h * p, f",{n},{k * p}")
    assert not [m for m in _step_sized_moves(text, b * h * p) if m[0] == "transpose"]


def test_granite_hybrid_pump_cell_shapes(one_chip, monkeypatch):
    """The decode program granite-4.0-h-small's cell serves: the batcher's pump
    over the paged layout at 64 slots x 2048, every published width, 10 layers
    of which one is attention, 36 held experts, bfloat16 weights and K/V, the
    whole arena (K/V blocks, state, tails) donated. Both kernels are in it
    (nine scans, one attention), the state leaf ([9, 65, 64, 128, 128]) comes
    back in its own buffer and nothing its size is copied, and the kernel's
    rows are reshapes of a step's [B, H, P] vectors: no transpose their size
    stands in the program."""
    from nnstreamer_tpu.models import granite_hybrid as gh
    from nnstreamer_tpu.models.serving import _PagedLayout, make_pump
    from nnstreamer_tpu.ops.pallas import ssm
    from nnstreamer_tpu.ops.pallas.paged_attention import make_paged_attention

    # the program asks jax's backend, which is the CPU here: compile the kernels
    monkeypatch.setattr(ssm, "interpret_default", lambda: False)
    b, nb = 64, 128
    cfg = gh.config_from_options(
        {"n_layers": "10", "experts_held": "36", "vocab": "50176"})
    bf16 = jnp.bfloat16
    family = gh.GraniteHybridFamily(cfg, bf16)
    attn = make_paged_attention(interpret=False, scale=cfg.attn_scale)
    pump = make_pump(_PagedLayout(family, attn), False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = described(jax.eval_shape(lambda: gh.init_params(cfg, 0, bf16)))
    arena = described(jax.eval_shape(lambda: family.arena(b * nb, BS, False, b)))
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert arena[2].shape == (9, b + 1, h // 2, n, 2 * p) and arena[2].dtype == f32
    vec, fvec = sds((b,), i32), sds((b,), f32)
    compiled = pump.lower(
        (params, None), vec, vec, sds((b,), jnp.bool_), arena,   # tok pos active
        sds((b, nb * BS), i32), vec, vec,                        # hist budget stop
        fvec, vec, fvec, sds((b, 2), jnp.uint32),                # the sampler's
        sds((b, nb), i32),                                       # tables
        n_steps=2,
    ).compile()
    text = compiled.as_text()
    assert "jit_impl" in text  # the name benchmark/configs select it by
    calls = re.findall(r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*",
                       text)
    assert sum("ssm_decode_step" in c for c in calls) == 9, len(calls)
    assert sum("paged_decode_attention" in c for c in calls) == 1
    assert not _big_moves(text, b * h * p * n, f",{n},{2 * p}")
    # K/V blocks, state, tails and history: donated, back in their own buffers
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert aliased.count("may-alias") + aliased.count("must-alias") == 5
    # beside a scan nothing the size of its [B, H x P] vectors is transposed;
    # ONE copy a layer re-tiles the projection's output (lanes of 8 on the
    # sublanes) into the kernel's rows. The [H, P, N] layout had six such
    # moves a layer and copied the whole tails leaf twice a step.
    moves = _step_sized_moves(text, b * h * p)
    assert not [m for m in moves if m[0] == "transpose"], moves
    assert len(moves) <= cfg.n_ssm, moves
    assert all(math.prod(int(x) for x in dims.split(",")) == b * h * p
               for _, dims in moves), moves
    # weights 9.5 GB + arena 3.0 GB + what a step holds besides: under the chip
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 14.5e9, total


# (n-slots, max-len) of the per-slot state the admit program rewrites
ADMIT_CELLS = {"olmo-1b": (16, 1024), "longcat-flash-chat": (64, 2048)}


@pytest.mark.parametrize("cell", list(ADMIT_CELLS))
def test_admit_program_cell_shapes(one_chip, cell):
    """The batcher's admission program (``jit_nns_admit``) at the cells'
    shapes: all seven per-slot arrays are donated and come back in their
    own buffers, and the history array is rewritten in place — no copy of
    anything its size in the compiled text."""
    from nnstreamer_tpu.models.serving import _ADMIT_COLS, _make_admit

    b, h = ADMIT_CELLS[cell]
    u32 = jnp.uint32
    shapes = (
        ((b,), i32), ((b,), i32), ((b,), f32), ((b,), i32), ((b,), f32),
        ((b, 2), u32), ((b, h), i32), ((b, h + _ADMIT_COLS), i32),
    )
    text = _make_admit(h).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    )).compile().as_text()
    assert "jit_nns_admit" in text
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    for i in range(7):
        assert f"{{{i}}}: ({i}, {{}}" in aliased, (i, aliased)
    copies = re.findall(
        rf"= \(?s32\[{b},{h}\][^=\n]*? (?:copy|copy-start)\(", text
    )
    assert not copies, copies


def test_nms_ssd_anchors(one_chip):
    from nnstreamer_tpu.ops.pallas.nms import nms

    _assert_kernel(_compile(
        lambda boxes, scores: nms(boxes, scores, 0.5, 100),
        one_chip, ((1917, 4), f32), ((1917,), f32),
    ))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint8])
def test_crop_and_resize_720p(one_chip, dtype):
    from nnstreamer_tpu.ops.pallas.image_kernels import crop_and_resize

    _assert_kernel(_compile(
        lambda image, boxes: crop_and_resize(image, boxes, 112, 112),
        one_chip, ((720, 1280, 3), dtype), ((8, 4), f32),
    ))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint8])
def test_resize_1080p_to_224(one_chip, dtype):
    from nnstreamer_tpu.ops.pallas.image_kernels import resize_bilinear

    _assert_kernel(_compile(
        lambda image: resize_bilinear(image, 224, 224),
        one_chip, ((1, 1080, 1920, 3), dtype),
    ))


def test_mobilenet_v2_224_forward(one_chip):
    """The flagship: ``__graft_entry__.entry()``'s MobileNet-v2 1.0
    224x224 forward, as the quick-start pipeline runs it."""
    import __graft_entry__ as graft

    fn, (example,) = graft.entry()
    text = _compile(fn, one_chip, (example.shape, example.dtype))
    assert "convolution" in text
