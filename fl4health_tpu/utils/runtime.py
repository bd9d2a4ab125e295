"""Process-level runtime facts shared by the entry scripts: where the XLA
compile cache lives, which device the initialized backend reports, and the
one-JSON-line convention launcher parents use to read a child's result.

Rule for anything that needs the chip: ONE process. A chip belongs to the
process that first touched the backend, so a launcher parent (``bench.py``
``main``, ``tools/flash_crossover.py``) must never call ``jax.devices()`` /
``jax.default_backend()`` before starting a child that needs the device.
"""

from __future__ import annotations

import json
import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache(
    default_dir: str | os.PathLike | None = None,
    min_compile_time_secs: float | None = None,
) -> str:
    """Place the persistent XLA compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, nothing is configured in
    code: JAX reads the variable itself, and an operator (or the machine
    the chip tool hands out) that placed the cache must not be overridden.
    Otherwise the cache goes to ``default_dir`` or ``<repo>/.jax_cache`` —
    a FIXED path inside the checkout, because the directory is part of how
    a later process finds the entries again (never a temp dir, pid or
    timestamp). ``min_compile_time_secs`` lowers JAX's persist threshold
    (the CPU test lane persists every sub-second compile); it too is left
    alone when the variable is set.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    path = str(default_dir) if default_dir else str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
    return path


def live_device_summary() -> dict:
    """Identity + published peaks of the ALREADY-initialized backend's
    first device. Shared by the observability run manifest and ``bench.py``
    provenance so the "which chip, what peak" policy lives in one place."""
    import jax

    from fl4health_tpu.observability import device_specs

    devices = jax.devices()
    d = devices[0]
    kind = getattr(d, "device_kind", "unknown")
    return {
        "platform": d.platform,
        "device_kind": kind,
        "device_count": len(devices),
        "accelerator": d.platform != "cpu",
        "peak_bf16_flops": device_specs.peak_bf16_flops(kind),
        "device_memory_bytes": device_specs.device_memory_bytes(d),
    }


def last_json_line(text: str) -> dict | None:
    """Parse the LAST valid JSON object line from child stdout (later lines
    supersede earlier partial/progress output)."""
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None
