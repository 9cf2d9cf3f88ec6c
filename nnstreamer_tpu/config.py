"""Layered configuration: env vars > ini file > hardcoded defaults.

Reference: gst/nnstreamer/nnstreamer_conf.{c,h} — priority "env-var >
/etc/nnstreamer.ini > hardcoded" (nnstreamer_conf.h:26-29), controlling
subplugin search paths, framework auto-detect priority per model extension,
and per-backend bool/string knobs (template nnstreamer.ini.in).

Env mapping: section ``filter`` key ``framework_priority`` is overridden by
``NNS_TPU_FILTER_FRAMEWORK_PRIORITY``. The ini path itself comes from
``NNS_TPU_CONF`` (default ``~/.config/nnstreamer_tpu.ini``, then
``/etc/nnstreamer_tpu.ini``). ``enable_envvar`` (default on) can disable the
env layer, mirroring the reference's meson option (meson_options.txt:36).
"""

from __future__ import annotations

import configparser
import os
import threading
from typing import Dict, List, Optional

_DEFAULTS: Dict[str, Dict[str, str]] = {
    "common": {
        "enable_envvar": "true",
        # comma list of allowed elements; empty = all (reference
        # element-restriction product whitelist, meson_options.txt:40-41)
        "restricted_elements": "",
    },
    "filter": {
        # search paths for out-of-tree backend plugins (python files defining
        # register()); colon separated
        "plugin_paths": "",
        # model-extension → backend auto-detection priority
        # (reference nnstreamer.ini.in:14-17 framework_priority_*)
        "framework_priority_stablehlo": "jax",
        "framework_priority_mlir": "jax",
        "framework_priority_pkl": "jax",
        "framework_priority_msgpack": "jax",
        "framework_priority_py": "custom",
        "framework_priority_tflite": "tflite,jax",
        # .pt/.pth = TorchScript (torch.jit.load); .pt2 (torch.export
        # archives) is NOT mapped — the torch backend can't load it
        "framework_priority_pt": "torch",
        "framework_priority_pth": "torch",
    },
    "decoder": {"plugin_paths": ""},
    "converter": {"plugin_paths": ""},
    "jax": {
        # default compute dtype for fused segments on TPU
        "compute_dtype": "bfloat16",
    },
    "edge": {
        "default_port": "3000",  # reference edge_common.h:36-37
        "timeout_sec": "10",  # reference tensor_query_common.h:28
    },
    "plane": {
        # serving-plane defaults (serving_plane/plane.py,
        # docs/serving-plane.md); per-filter plane-* properties
        # override. Env: NNS_TPU_PLANE_MAX_BATCH etc.
        "max_batch": "8",
        "timeout_ms": "1.0",
        # single | shard (data-parallel mesh) | replicas (K failover
        # copies, parallel/replicas.py semantics)
        "mode": "single",
        # devices backing the plane: mesh size (shard) / replica count
        "devices": "1",
        # replica health (mode=replicas): consecutive device faults
        # that bench a replica, and probe cadence for re-admission
        "unhealthy_after": "3",
        "probe_every": "64",
        # a submit with no service inside this window fails typed
        # (service thread dead / program wedged), never hangs a node
        "submit_timeout_s": "30",
        # Hermes placement bound for place_pipeline (placement.py):
        # bytes per device, K/M/G suffixes accepted; empty = the
        # planner requires an explicit bound argument
        "memory_per_device": "",
    },
    "llm": {
        # continuous-batching LLM serving defaults
        # (tensor_llm_serversink props override; docs/llm-serving.md).
        # kv_layout: slot (one contiguous worst-case cache per slot) |
        # paged (block arena + per-request block tables with prefix
        # sharing, chunked prefill and preemption-by-eviction)
        "kv_layout": "slot",
        # tokens per KV block (paged); must divide prompt-len/max-len
        "block_size": "16",
        # total usable blocks in the arena (paged); empty = enough for
        # every slot at max-len (no memory saving — size it BELOW that
        # to serve more live requests at the same HBM)
        "kv_blocks": "",
        # prefill buckets a pump may spend (paged chunked prefill).
        # 0 = follow the queue: as many buckets as jobs wait at the
        # pump's start (at least 1), so admission keeps pace with the
        # slots that free and a decoding slot waits for the buckets of
        # the requests queued; N >= 1 caps a pump at N buckets (the
        # bound on the largest decode stall)
        "prefill_chunks": "0",
        # declared KV memory bound for nns-lint NNS-W115 (bytes, K/M/G
        # suffixes); empty = lint stays silent
        "memory_bound": "",
    },
    "executor": {
        # micro-batching defaults for fused segments / batchable filters
        # (pipeline/batching.py); per-element properties on tensor_filter
        # (batching=, max-batch=, ...) override. Env:
        # NNS_TPU_EXECUTOR_BATCHING etc.
        "batching": "false",
        "max_batch": "8",
        "batch_timeout_ms": "1.0",
        # comma list of padded batch sizes; empty = 1,2,4,...,max_batch
        "batch_buckets": "",
        # fault tolerance defaults (pipeline/faults.py); per-element
        # on-error/retry-max/retry-backoff-ms properties override. Env:
        # NNS_TPU_EXECUTOR_ON_ERROR etc.
        "on_error": "stop",
        "retry_max": "3",
        "retry_backoff_ms": "10.0",
        "retry_backoff_cap_ms": "1000.0",
        # stall watchdog: >0 arms the executor monitor thread that turns
        # a no-progress-with-queued-data hang into PipelineStallError
        "watchdog_timeout_ms": "0",
        # device-resilience defaults (pipeline/device_faults.py,
        # docs/resilience.md); per-element oom-policy/device-fallback
        # properties override. Env: NNS_TPU_EXECUTOR_OOM_POLICY etc.
        "oom_policy": "degrade",
        "device_fallback": "true",
        "device_fallback_after": "3",
        "device_probe_every": "64",
        "oom_reprobe_ms": "30000.0",
        # resident streaming executor (pipeline/transfer.py,
        # docs/streaming.md): ring_depth = in-flight frames per device
        # node (H2D of N+1 / compute of N / D2H of N-1 overlap; 1 =
        # synchronous dispatch-and-deliver), donate = hand node-owned
        # activation buffers (staged uploads, stacked batch windows) to
        # the fused program for reuse. Per-element ring-depth property
        # overrides. Env: NNS_TPU_EXECUTOR_RING_DEPTH etc.
        "ring_depth": "2",
        "donate": "true",
        # whole-chain resident programs (pipeline/chain_program.py,
        # docs/chain-analysis.md "Compiled chains"): chain_mode=auto
        # compiles every eligible multi-segment chain into ONE jitted
        # program dispatched once per unrolled window of chain_unroll
        # frames (clamped by the OOM bucket governor rung and the W124
        # transient-HBM bound); off keeps the per-node parity path.
        # Per-element chain-mode property overrides. Env:
        # NNS_TPU_EXECUTOR_CHAIN_MODE / NNS_TPU_EXECUTOR_CHAIN_UNROLL.
        "chain_mode": "auto",
        "chain_unroll": "4",
        # nns-san runtime sanitizer (pipeline/sanitize.py): instrumented
        # channels assert negotiated-spec conformance per frame, latch
        # offered == delivered + dropped + routed per node at EOS, watch
        # lock order, poison batch pad rows, and report leaked threads.
        # The NNS_TPU_SANITIZE env var is the documented one-knob opt-in
        # (checked before this layered key).
        "sanitize": "false",
        # nns-obs live telemetry (obs/): `metrics` turns on per-element
        # latency/queue-wait/queue-depth histograms (p50/p95/p99 in
        # Executor.stats and nns-launch --stats); `metrics_port` > 0
        # additionally serves /metrics (Prometheus) + /metrics.json
        # (nns-top) from a background thread. NNS_TPU_METRICS /
        # NNS_TPU_METRICS_PORT are the documented one-knob env opt-ins
        # (checked before these layered keys).
        "metrics": "false",
        "metrics_port": "0",
        # bind address for the exposition endpoint: loopback unless the
        # operator explicitly widens it (the endpoint has no auth)
        "metrics_host": "127.0.0.1",
    },
}

_ENV_PREFIX = "NNS_TPU_"


class Config:
    """Thread-safe layered config with the reference's 3-level priority."""

    def __init__(self, ini_path: Optional[str] = None):
        self._lock = threading.Lock()
        self._parser = configparser.ConfigParser()
        self._loaded_path: Optional[str] = None
        self.load(ini_path)

    def load(self, ini_path: Optional[str] = None) -> None:
        with self._lock:
            self._parser = configparser.ConfigParser()
            candidates = [
                ini_path,
                os.environ.get(_ENV_PREFIX + "CONF"),
                os.path.expanduser("~/.config/nnstreamer_tpu.ini"),
                "/etc/nnstreamer_tpu.ini",
            ]
            for c in candidates:
                if c and os.path.isfile(c):
                    self._parser.read(c)
                    self._loaded_path = c
                    break

    @property
    def env_enabled(self) -> bool:
        raw = self._layered("common", "enable_envvar", use_env=False)
        return raw.strip().lower() in ("1", "true", "yes", "on")

    def _layered(self, section: str, key: str, use_env: bool = True) -> str:
        if use_env:
            env_key = f"{_ENV_PREFIX}{section.upper()}_{key.upper()}"
            if env_key in os.environ:
                return os.environ[env_key]
        if self._parser.has_option(section, key):
            return self._parser.get(section, key)
        return _DEFAULTS.get(section, {}).get(key, "")

    def get(self, section: str, key: str, default: str = "") -> str:
        val = self._layered(section, key, use_env=self.env_enabled)
        return val if val != "" else default

    def get_bool(self, section: str, key: str, default: bool = False) -> bool:
        raw = self.get(section, key, "")
        if raw == "":
            return default
        return raw.strip().lower() in ("1", "true", "yes", "on")

    def get_int(self, section: str, key: str, default: int = 0) -> int:
        raw = self.get(section, key, "")
        try:
            return int(raw)
        except ValueError:
            return default

    def get_list(self, section: str, key: str, sep: str = ",") -> List[str]:
        raw = self.get(section, key, "")
        return [p.strip() for p in raw.split(sep) if p.strip()]

    def plugin_paths(self, kind: str) -> List[str]:
        """Search paths for out-of-tree subplugins of a kind
        (reference nnsconf_get_fullpath search-path machinery)."""
        return self.get_list(kind, "plugin_paths", sep=":")

    def framework_priority(self, model_ext: str) -> List[str]:
        """Backend priority list for a model file extension
        (reference tensor_filter_common.c:1155-1218 auto-detection)."""
        return self.get_list("filter", f"framework_priority_{model_ext.lstrip('.')}")


_global: Optional[Config] = None
_global_lock = threading.Lock()


def conf() -> Config:
    """Global config singleton (reference nnsconf_loadconf lazy-load)."""
    global _global
    with _global_lock:
        if _global is None:
            _global = Config()
        return _global


def reload_conf(ini_path: Optional[str] = None) -> Config:
    global _global
    with _global_lock:
        _global = Config(ini_path)
        return _global
