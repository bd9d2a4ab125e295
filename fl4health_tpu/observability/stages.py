"""Stage naming for the aggregation spine — ``fl_stage::<name>`` scopes.

Which stage of the clip -> quantize -> top-k -> robust-aggregate ->
server-update spine the device spends its time in is read from a profiler
trace, not from a cost model: this module gives each spine stage a name
that survives into the compiled program (each op's ``op_name`` metadata)
and from there into the trace's op metadata, where
``benchmarks/xplane_meta.py`` + ``layer_metrics/stage_common.py`` and
``tools/roofline_report.py`` sum MEASURED device time per stage.

Mechanism: :func:`stage` wraps a code region in ``jax.named_scope`` with
the ``fl_stage::`` prefix. Named scopes are **metadata only** — they change
neither the math nor XLA's optimization decisions (pinned by
tests/observability/test_stage_attribution.py, whose reference arm traces
under :func:`disabled`; nothing else turns the scopes off). Autodiff and
``vmap``/``scan`` transforms preserve the name stack, so a stage's
backward-pass ops carry the same stage as its forward ops.

The canonical spine stages (:data:`SPINE_STAGES`):

- ``local_train``   — the engine's train-step scan (clients/engine.py)
- ``dp_clip``       — fused per-example clip+reduce (kernels/dp_clip.py)
- ``rotation``      — randomized-Hadamard encode/decode (compression/codecs.py)
- ``topk``          — global magnitude top-k selection (compression/codecs.py)
- ``quantize``      — stochastic uniform quantization (compression/codecs.py)
- ``robust_aggregate`` — Byzantine-robust combinators (resilience/aggregators.py)
- ``server_update`` — the strategy's aggregate/server step, broken out
  explicitly since it is what cross-replica weight-update sharding
  optimizes (Xu et al., arXiv:2004.13336)
- ``cohort_exchange`` — the in-graph cohort gather/scatter of the chunked
  registry window (server/simulation.py)
"""

from __future__ import annotations

import contextlib
import re
from typing import Iterator

# The marker the trace readers look for in the ops' name stacks. "::"
# cannot appear in a user module/function name the way "/" separators do,
# so the prefix never collides with ordinary scope components.
STAGE_PREFIX = "fl_stage::"

# Canonical spine stage names, in pipeline order.
SPINE_STAGES = (
    "local_train",
    "dp_clip",
    "rotation",
    "topk",
    "quantize",
    "robust_aggregate",
    "server_update",
    "cohort_exchange",
)

_STAGE_RE = re.compile(re.escape(STAGE_PREFIX) + r"([A-Za-z0-9_.\-]+)")

_enabled = True


@contextlib.contextmanager
def disabled() -> Iterator[None]:
    """Trace without stage scopes: the reference arm of the bit-identity
    tests, and nothing else."""
    global _enabled
    prev = _enabled
    _enabled = False
    try:
        yield
    finally:
        _enabled = prev


@contextlib.contextmanager
def stage(name: str) -> Iterator[None]:
    """Scope a traced code region as spine stage ``name``.

    Named scopes are trace-time metadata: nothing runs for them. ``jax``
    is imported lazily so tools can import this module's parsing helpers
    without a backend."""
    if not _enabled:
        yield
        return
    import jax

    with jax.named_scope(STAGE_PREFIX + name):
        yield


def stage_of(op_name: str | None) -> str | None:
    """The spine stage an HLO/trace ``op_name`` path belongs to, or None.

    Takes the LAST ``fl_stage::`` component on the path — scopes nest
    (``server_update`` wraps ``robust_aggregate`` wraps nothing), and the
    innermost name is the most specific attribution."""
    if not op_name:
        return None
    hits = _STAGE_RE.findall(op_name)
    return hits[-1] if hits else None
