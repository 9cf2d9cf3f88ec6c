"""XLA's persistent compilation cache, placed from outside.

The checkpoint/resume analogue for an inference framework (SURVEY.md
§5.4: compiled-executable persistence): a restarted pipeline or LLM
server replays its programs from disk and reaches steady state in
seconds instead of a cold recompile (docs/resilience.md).

One rule for where it lives. If ``JAX_COMPILATION_CACHE_DIR`` is set,
jax itself reads it and this module names no directory at all — whoever
runs the program owns the placement. Otherwise the cache is on at a
FIXED path inside the checkout, ``<repo>/.jax_cache``: the directory is
part of every entry's key, so a path that moved (a temp name, a pid, a
timestamp) would never hit.

:func:`ensure_compile_cache` is called before the first program is
built on each path that builds programs — ``JaxBackend.open`` for
pipelines, ``ContinuousBatcher.__init__`` for LLM serving — never while
a module is imported: it asks ``jax.default_backend()``, which starts
the backend.
"""

from __future__ import annotations

import hashlib
import os
import platform
import threading

import jax

from nnstreamer_tpu.log import get_logger

_log = get_logger("compile_cache")

#: the fixed default: ``<repo>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

_lock = threading.Lock()
_done = False


def _cpu_host_fingerprint() -> str:
    """CPU AOT cache entries embed the COMPILING host's feature set yet
    reload on any host (cpu_aot_loader then warns about mismatched
    machine features and may SIGILL mid-inference) — a cache baked on
    one machine must never be replayed on a different one. TPU entries
    key on the device kind already and stay SHARED."""
    fp = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((ln for ln in f if ln.startswith("flags")), "")
    except OSError:
        flags = ""
    if flags:
        fp += "-" + hashlib.sha1(flags.encode()).hexdigest()[:12]
    return fp


def ensure_compile_cache() -> None:
    """Turn the persistent compilation cache on, once per process.

    Corruption tolerant by construction: cache errors are forced
    non-fatal (``jax_raise_persistent_cache_errors=False``), so a
    truncated/garbage entry logs and recompiles — a stale cache can
    slow a restart down, never crash it."""
    global _done
    with _lock:
        if _done:
            return
        _done = True
        on_cpu = jax.default_backend() == "cpu"
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            cache_dir = DEFAULT_DIR
            if on_cpu:
                cache_dir = os.path.join(cache_dir, _cpu_host_fingerprint())
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # the CPU compiles a small program faster than it reads one
        # back; a TPU does not, and a pipeline builds hundreds: caching
        # them all took a warm chip_smoke.py from 42 to 9 compile
        # seconds (PERF.md, PR 21)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            0.5 if on_cpu else 0.0,
        )
        jax.config.update("jax_raise_persistent_cache_errors", False)
        _log.info(
            "persistent compilation cache at %s",
            jax.config.jax_compilation_cache_dir,
        )
