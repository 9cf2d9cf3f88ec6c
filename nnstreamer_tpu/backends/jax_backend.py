"""The primary TPU backend: models as pure jax functions compiled by XLA.

This is the analogue slot of the reference's tensorflow-lite subplugin (its
default CPU engine, ext/nnstreamer/tensor_filter/tensor_filter_tensorflow_
lite.cc) — but TPU-first: a model is a pure function + params pytree, jitted
once at open (the reference's fw->open = "model load, device init",
SURVEY.md §3.1), with shapes fixed by negotiation so XLA compiles exactly
one executable. The un-jitted function is exposed for fusion with adjacent
transform/decoder stages.

Model sources (by ``model=`` value):

- ``zoo:<name>`` — built-in model zoo (nnstreamer_tpu/models/zoo.py), e.g.
  ``zoo:mobilenet_v2``. Options via custom string
  (``custom="num_classes:1001,width:1.0"``).
- ``<path>.py`` — user script defining
  ``get_model(options: dict) -> (fn, input_spec | None)`` where ``fn`` is a
  pure traceable callable ``(*tensors) -> tensor | tuple``.
- ``<path>.jaxexport`` / ``<path>.stablehlo`` — a serialized
  ``jax.export.Exported`` artifact (StableHLO); the TPU equivalent of
  loading a .tflite flatbuffer.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu import registry
from nnstreamer_tpu.backends.base import Backend, BackendError, FilterProps
from nnstreamer_tpu.compile_cache import ensure_compile_cache
from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.tensors.spec import DType, TensorSpec, TensorsSpec

_log = get_logger("backends.jax")


def _spec_from_avals(avals) -> TensorsSpec:
    return TensorsSpec(
        tuple(
            TensorSpec(tuple(int(d) for d in a.shape), DType.from_any(a.dtype))
            for a in avals
        )
    )


def _as_tuple(x) -> Tuple[Any, ...]:
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


@registry.filter_backend("jax")
class JaxBackend(Backend):
    """framework=jax: jitted pure-function inference on the default device
    (TPU when present), optionally sharded over a mesh (see parallel/)."""

    name = "jax"
    DEVICE_INPUT_OK = True  # invoke() device_puts/reshards its inputs

    def __init__(self) -> None:
        super().__init__()
        self._fn: Optional[Callable] = None
        self._jitted: Optional[Callable] = None
        self._in_spec: Optional[TensorsSpec] = None
        self._out_spec: Optional[TensorsSpec] = None
        self._device = None
        self._shardings = None  # (in_shardings, out_shardings) when sharded
        self._mesh_spec: Optional[str] = None  # e.g. "dp2tp4" (mesh: option)
        self._mesh = None
        self._apply: Optional[Callable] = None  # params-explicit fn
        self._params = None
        self._param_shardings = None
        self._placed_params = None
        self._params_explicit = False

    # -- lifecycle ---------------------------------------------------------
    def open(self, props: FilterProps) -> None:
        ensure_compile_cache()
        self.props = props
        path = props.model_path
        options = props.custom_dict()
        # per-stage device placement (SURVEY.md §7 build order 5): a
        # pipeline shards across chips by pinning each filter to a device;
        # inter-stage hops are device_put transfers riding ICI, replacing
        # the reference's host TCP between pipeline segments
        if "device" in options:
            devs = jax.devices()
            idx = int(options["device"])
            if not (0 <= idx < len(devs)):
                raise BackendError(
                    f"jax: device:{idx} out of range (have {len(devs)})"
                )
            self._device = devs[idx]
        # mesh-sharded filter (the TP/DP inference story): custom
        # "mesh:dp2tp4" pjits this filter over a named device mesh —
        # replaces the reference's accelerator-string device selection
        # (tensor_filter_common.c:451-) with XLA GSPMD partitioning
        mesh_spec = options.get("mesh") or self._parse_accel_mesh(
            props.accelerator
        )
        if mesh_spec:
            if self._device is not None:
                raise BackendError("jax: device: and mesh: are exclusive")
            self._mesh_spec = mesh_spec
        if path.startswith("zoo:"):
            self._open_zoo(path[len("zoo:"):], options)
        elif path.endswith(".py"):
            self._open_script(path, options)
        elif path.endswith((".jaxexport", ".stablehlo", ".hlo")):
            self._open_exported(path)
        elif path.endswith(".tflite"):
            self._open_tflite(path)
        else:
            raise BackendError(f"jax: unsupported model source {path!r}")
        if self._in_spec is None:
            self._in_spec = props.input_spec
        if self._in_spec is not None and self._in_spec.is_static:
            self._compile()

    def _open_zoo(self, name: str, options) -> None:
        from nnstreamer_tpu.models import zoo

        opts = {k: v for k, v in options.items() if k not in ("device", "mesh")}
        m = zoo.get(name, **opts)
        self._fn = m.fn
        self._in_spec = m.input_spec
        self._apply = m.apply
        self._params = m.params

    def _open_script(self, path: str, options) -> None:
        if not os.path.isfile(path):
            raise BackendError(f"jax: model script not found: {path}")
        spec = importlib.util.spec_from_file_location(
            f"nns_tpu_jaxmodel_{abs(hash(path))}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if not hasattr(module, "get_model"):
            raise BackendError(f"jax: {path} defines no get_model(options)")
        fn, in_spec = module.get_model(options)
        self._fn = fn
        self._in_spec = in_spec

    def _open_tflite(self, path: str) -> None:
        """framework=jax model=<f>.tflite: decode the flatbuffer
        (tools/tflite_parse) and trace the whole graph as ONE jnp
        program (tools/tflite_exec) — the reference's canonical .tflite
        fixtures run TPU-native through XLA with no interpreter in the
        invoke loop (vs tensor_filter_tensorflow_lite.cc's per-op CPU
        dispatch). Quantized graphs run fake-quant float (exact weight
        dequant + per-tensor activation grids)."""
        if not os.path.isfile(path):
            raise BackendError(f"jax: tflite model not found: {path}")
        from nnstreamer_tpu.tools.tflite_exec import TFLiteProgram

        try:
            prog = TFLiteProgram(path)
            # trace NOW: tracing is lazy, so an unsupported op would
            # otherwise escape later (at _compile/invoke) as a raw
            # NotImplementedError instead of the backend error contract
            jax.eval_shape(prog.trace, *(
                jax.ShapeDtypeStruct(s, d)
                for s, d in zip(prog.input_shapes, prog.input_dtypes)
            ))
        except NotImplementedError as exc:
            raise BackendError(f"jax: cannot compile {path}: {exc}") from exc
        self._fn = lambda *ts: tuple(prog.trace(*ts))
        self._in_spec = TensorsSpec(tuple(
            TensorSpec(tuple(int(d) for d in s), DType.from_any(dt))
            for s, dt in zip(prog.input_shapes, prog.input_dtypes)
        ))

    def _open_exported(self, path: str) -> None:
        with open(path, "rb") as f:
            blob = f.read()
        exported = jax.export.deserialize(bytearray(blob))
        self._fn = lambda *tensors: exported.call(*tensors)
        self._in_spec = _spec_from_avals(exported.in_avals)

    # -- compile -----------------------------------------------------------
    @staticmethod
    def _parse_accel_mesh(accelerator: str) -> Optional[str]:
        """``accelerator=true:tpu:mesh=dp2tp4`` → ``dp2tp4`` (the reference's
        accelerator-string grammar, extended with a mesh clause)."""
        for part in (accelerator or "").split(":"):
            part = part.strip()
            if part.startswith("mesh="):
                return part[len("mesh="):]
        return None

    def _build_mesh_shardings(self) -> None:
        """Turn the mesh spec + negotiated input spec into jit shardings.

        Any GSPMD sharding annotation compiles to a *correct* program (XLA
        inserts the collectives); the choices here are the perf defaults:
        batch dim over ``dp``, last weight dim over ``tp`` (column-parallel
        matmuls/convs), everything else replicated.
        """
        import math
        import re

        from jax.sharding import NamedSharding, PartitionSpec as P

        from nnstreamer_tpu.parallel.mesh import make_mesh

        pairs = re.findall(r"([a-z]+)(\d+)", self._mesh_spec)
        if not pairs or "".join(f"{a}{s}" for a, s in pairs) != self._mesh_spec:
            raise BackendError(
                f"jax: bad mesh spec {self._mesh_spec!r} (want e.g. dp2tp4)"
            )
        axes = tuple(a for a, _ in pairs)
        sizes = tuple(int(s) for _, s in pairs)
        n = math.prod(sizes)
        if n > len(jax.devices()):
            raise BackendError(
                f"jax: mesh {self._mesh_spec} needs {n} devices, "
                f"have {len(jax.devices())}"
            )
        mesh = make_mesh(n, axes=axes, shape=sizes)
        ax = dict(zip(axes, sizes))
        dp, tp = ax.get("dp", 1), ax.get("tp", 1)
        rep = NamedSharding(mesh, P())
        in_sh = []
        for t in self._in_spec:
            if dp > 1 and len(t.shape) >= 1 and t.shape[0] % dp == 0:
                in_sh.append(
                    NamedSharding(mesh, P("dp", *([None] * (len(t.shape) - 1))))
                )
            else:
                in_sh.append(rep)
        param_sh = None
        if self._apply is not None and self._params is not None:
            def rule(leaf):
                shp = tuple(getattr(leaf, "shape", ()))
                if tp > 1 and len(shp) >= 2 and shp[-1] % tp == 0 and shp[-1] >= tp:
                    return NamedSharding(
                        mesh, P(*([None] * (len(shp) - 1)), "tp")
                    )
                return rep

            param_sh = jax.tree_util.tree_map(rule, self._params)
        elif tp > 1:
            _log.warning(
                "jax: mesh %s has tp>1 but model exposes no params-explicit "
                "apply; falling back to input sharding only", self._mesh_spec,
            )
        self._mesh = mesh
        self._shardings = (tuple(in_sh), None)
        self._param_shardings = param_sh

    def _compile(self) -> None:
        assert self._fn is not None and self._in_spec is not None
        fn = self._fn
        wrapped = lambda *tensors: _as_tuple(fn(*tensors))  # noqa: E731
        if self._mesh_spec:
            self._build_mesh_shardings()
        dummies = [
            jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype) for t in self._in_spec
        ]
        sharded = (
            self._shardings is not None
            and self._param_shardings is not None
        )
        if sharded or (
            self._apply is not None
            and self._params is not None
            and self._shardings is None
        ):
            # params-explicit invoke (docs/streaming.md): weights are
            # device_put ONCE here — sharded across the mesh, or pinned
            # to the single target device — and passed as explicit jit
            # arguments, so every compiled entry (per shape, per batch
            # bucket) shares the same resident copy instead of
            # re-embedding the params as per-program constants:
            # steady-state invokes touch no host weight memory at all
            apply = self._apply
            wrapped_p = lambda p, *xs: _as_tuple(apply(p, *xs))  # noqa: E731
            jit_kwargs = {}
            placement = None
            if sharded:
                placement = self._param_shardings
                jit_kwargs = dict(
                    in_shardings=(self._param_shardings, *self._shardings[0])
                )
                if self._shardings[1] is not None:
                    jit_kwargs["out_shardings"] = self._shardings[1]
            elif self._device is not None:
                placement = self._device
                jit_kwargs = dict(
                    out_shardings=jax.sharding.SingleDeviceSharding(
                        self._device
                    )
                )
            self._jitted = jax.jit(wrapped_p, **jit_kwargs)
            self._placed_params = jax.device_put(self._params, placement)
            self._params_explicit = True
            outs = jax.eval_shape(wrapped_p, self._params, *dummies)
        else:
            jit_kwargs = {}
            if self._shardings is not None:
                jit_kwargs = dict(in_shardings=self._shardings[0])
                if self._shardings[1] is not None:
                    jit_kwargs["out_shardings"] = self._shardings[1]
            elif self._device is not None:
                single = jax.sharding.SingleDeviceSharding(self._device)
                jit_kwargs = dict(out_shardings=single)
            self._jitted = jax.jit(wrapped, **jit_kwargs)
            self._params_explicit = False
            # shape inference without running (reference getModelInfo): one
            # abstract evaluation of the jitted function
            outs = jax.eval_shape(wrapped, *dummies)
        self._out_spec = _spec_from_avals(_as_tuple(outs))

    def plane_fn(self):
        """``(fn, device)`` for the serving plane (serving_plane/
        sharding.py). Unlike :meth:`traceable_fn` — which refuses when a
        device pin makes FUSION illegal — the plane builds its own
        program and honors the pin itself, so ``plane= device=N``
        batches on chip N instead of silently degrading to a per-frame
        host loop. Mesh-sharded state still returns (None, None): the
        plane's own ``plane-mode=shard`` is the sharded path."""
        fn = self._fn
        if fn is None or self._mesh_spec or self._shardings is not None:
            return None, None
        return (lambda tensors: _as_tuple(fn(*tensors))), self._device

    def pin_device(self, idx: int) -> None:
        """Post-open per-stage placement — the Hermes planner's entry
        (serving_plane/placement.py): pin this stage to device ``idx``
        and recompile so weights land there once. Inter-stage hops then
        ride async device_put (ICI on real chips). The ``device:``
        custom option builds the same state at open; this hook exists
        because the planner runs after backends opened (it reuses them
        for the memory estimate)."""
        devs = jax.devices()
        if not (0 <= idx < len(devs)):
            raise BackendError(
                f"jax: device {idx} out of range (have {len(devs)})"
            )
        if self._mesh_spec or self._shardings is not None:
            raise BackendError("jax: device pin and mesh are exclusive")
        self._device = devs[idx]
        if self._in_spec is not None and self._in_spec.is_static \
                and self._jitted is not None:
            self._compile()

    def set_shardings(
        self, in_shardings, out_shardings=None, param_shardings=None
    ) -> None:
        """Install jit shardings programmatically (the parallel layer's
        entry; the ``mesh:`` custom option builds the same state from a
        spec string)."""
        self._shardings = (tuple(in_shardings), out_shardings)
        self._param_shardings = param_shardings
        self._mesh_spec = None  # explicit shardings override the spec string
        if self._in_spec is not None and self._in_spec.is_static:
            self._compile()

    # -- negotiation -------------------------------------------------------
    def get_model_info(self) -> Tuple[TensorsSpec, TensorsSpec]:
        if self._in_spec is None:
            raise BackendError("jax: input spec unknown (shape-polymorphic "
                               "model needs set_input_info)")
        if self._out_spec is None:
            if not self._in_spec.is_static:
                raise BackendError(f"jax: input spec not static: {self._in_spec}")
            self._compile()
        return self._in_spec, self._out_spec

    def set_input_info(self, in_spec: TensorsSpec) -> TensorsSpec:
        if not in_spec.is_static:
            raise BackendError(f"jax: spec must be static, got {in_spec}")
        self._in_spec = in_spec
        self._compile()
        return self._out_spec

    # -- execution ---------------------------------------------------------
    def invoke(self, tensors: Tuple[Any, ...]) -> Tuple[Any, ...]:
        if self._jitted is None:
            self.get_model_info()
        # validate against the negotiated spec (reference tensor_filter.c:592)
        # — a silent mismatch would retrace/recompile per frame.
        if len(tensors) != self._in_spec.num_tensors:
            raise BackendError(
                f"jax: expected {self._in_spec.num_tensors} tensors, got {len(tensors)}"
            )
        if not (self.props is not None and self.props.invoke_dynamic):
            for t, s in zip(tensors, self._in_spec):
                if tuple(t.shape) != s.shape:
                    raise BackendError(
                        f"jax: input shape {tuple(t.shape)} != negotiated {s.shape}"
                    )
        # invoke-dynamic: per-frame shapes may drift (e.g. tensor_crop
        # output feeding a size-agnostic model); jax.jit retraces per new
        # shape and caches each executable
        if self._device is not None:
            # cross-stage hop: async device→device transfer (ICI on TPU)
            tensors = tuple(jax.device_put(t, self._device) for t in tensors)
        elif self._shardings is not None:
            # reshard inputs arriving from any placement (committed
            # single-device arrays from an upstream stage included) onto
            # this filter's mesh; device_put is async and rides ICI
            tensors = tuple(
                jax.device_put(t, s)
                for t, s in zip(tensors, self._shardings[0])
            )
        if self._params_explicit:
            return self._jitted(self._placed_params, *tensors)
        return self._jitted(*tensors)

    def traceable_fn(self):
        fn = self._fn
        if fn is None:
            return None
        if self._device is not None or self._shardings is not None or self._mesh_spec:
            # a device-pinned or mesh-sharded stage is a fusion barrier:
            # fusing it into a neighbor's XLA program would silently drop
            # the placement/partitioning
            return None
        return lambda tensors: _as_tuple(fn(*tensors))

    def warmup(self) -> None:
        """Compile + run once on zeros (first compile is slow on TPU; do it
        before streaming starts, like the reference loads the model at
        PAUSED, not on the first frame)."""
        in_spec, _ = self.get_model_info()
        zeros = tuple(jnp.zeros(t.shape, t.dtype.np_dtype) for t in in_spec)
        out = self.invoke(zeros)
        jax.block_until_ready(out)
