"""Dispatch gating for the Pallas kernels: every dual-path dispatch
site asks the same :func:`pallas_ok` question before committing to a
kernel, and the same :func:`interpret_default` one before running it.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Optional, Tuple

_log = logging.getLogger("nnstreamer_tpu.ops.pallas")

#: env escape hatch: force every dual-path op onto its jnp/XLA fallback
#: (read directly, not through conf() — it must work before any config
#: is loaded, e.g. for an --engage fallback drill)
DISABLE_ENV = "NNS_TPU_PALLAS_DISABLE"


def pallas_ok(kernel: str, dtype: Optional[Any] = None) -> Tuple[bool, str]:
    """May ``kernel`` take the Pallas path for ``dtype`` inputs?

    Returns ``(ok, reason)``; a False verdict is logged once per call
    site decision so a degraded pipeline says WHY it fell back instead
    of silently running jnp (or worse, raising a trace-time Mosaic
    error on an unsupported dtype — the registry's per-kernel dtype
    list is the support contract, satellite fix of PR 19).
    """
    if os.environ.get(DISABLE_ENV, "").strip() not in ("", "0"):
        reason = f"{DISABLE_ENV} set: pallas disabled process-wide"
        _log.warning("%s: %s — using jnp fallback", kernel, reason)
        return False, reason
    if dtype is not None:
        from nnstreamer_tpu.ops.pallas import registry

        if not registry.supports_dtype(kernel, dtype):
            spec = registry.find(kernel)
            supported = ", ".join(spec.dtypes) if spec else "?"
            reason = (
                f"dtype {str(dtype)} outside registered support"
                f" ({supported})"
            )
            _log.warning("%s: %s — using jnp fallback", kernel, reason)
            return False, reason
    return True, ""


def interpret_default() -> bool:
    """Pallas kernels run compiled on a TPU backend and through the
    interpreter everywhere else (the CPU test mesh). The one home of
    that choice: every kernel factory, dispatch site and registry
    ``run_case`` asks here, so the same parity sweep that interprets on
    the CPU checks the Mosaic-compiled kernels on a chip."""
    import jax

    return jax.default_backend() != "tpu"
