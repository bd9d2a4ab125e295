"""Run manifest — the provenance record served next to the metrics.

A scraped ``/metrics`` page is only interpretable with its context: which
jax/jaxlib, which backend and chip, how many devices, which execution mode
``fit()`` chose (and why), whether buffer donation was gated off, and a
stable hash of the run configuration so two scrapes can be matched to one
experiment. ``bench.py`` embeds similar provenance in its artifacts; this
module is the one implementation both the live scrape endpoint
(``observability/exposition.py``) and artifact writers share.

Everything here is a plain-JSON dict of host facts — no device work, no
per-round cost. ``config_hash`` is order-insensitive (canonical JSON), so
logically-equal configs hash equal across processes.
"""

from __future__ import annotations

import hashlib
import json
import platform
from typing import Any, Mapping


def config_hash(config: Mapping[str, Any]) -> str:
    """Short stable digest of a JSON-able config mapping (sorted keys,
    non-JSON leaves stringified) — an experiment identity, not a secret."""
    canonical = json.dumps(config, sort_keys=True, default=str,
                           separators=(",", ":"))
    return hashlib.sha256(canonical.encode("utf-8")).hexdigest()[:16]


def device_facts() -> dict[str, Any]:
    """Backend/device identity from the live (already-initialized) jax
    runtime — ``utils/runtime.live_device_summary`` (the one home of the
    "which chip, what peak" policy) plus the process-level facts only the
    manifest needs."""
    import jax

    from fl4health_tpu.utils.runtime import live_device_summary

    return {
        "backend": jax.default_backend(),
        "process_count": jax.process_count(),
        **live_device_summary(),
    }


def run_manifest(
    *,
    execution_mode: str | None = None,
    execution_mode_reason: str | None = None,
    donation: bool | None = None,
    mesh: Any = None,
    config: Mapping[str, Any] | None = None,
    extra: Mapping[str, Any] | None = None,
) -> dict[str, Any]:
    """Assemble the run manifest dict.

    ``donation``: whether the round programs donate their state buffers
    (False on CPU — see ``simulation._donate_argnums``). ``mesh``: a
    ``jax.sharding.Mesh`` (described via ``parallel.mesh.mesh_descriptor``)
    or an already-built descriptor dict. ``config``: JSON-able run config;
    stored hashed (``config_hash``) plus inline for human readers.
    """
    import jax
    import jaxlib

    mani: dict[str, Any] = {
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "python_version": platform.python_version(),
        **device_facts(),
    }
    if execution_mode is not None:
        mani["execution_mode"] = execution_mode
    if execution_mode_reason is not None:
        mani["execution_mode_reason"] = execution_mode_reason
    if donation is not None:
        mani["donation"] = bool(donation)
    if mesh is not None:
        if isinstance(mesh, Mapping):
            mani["mesh"] = dict(mesh)
        else:
            from fl4health_tpu.parallel.mesh import mesh_descriptor

            mani["mesh"] = mesh_descriptor(mesh)
    if config is not None:
        mani["config"] = dict(config)
        mani["config_hash"] = config_hash(config)
    if extra:
        mani.update(extra)
    return mani
