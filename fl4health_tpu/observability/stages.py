"""The program's names in a profiler trace: ``fl_stage::<name>`` for the
stages of a round, ``fl_layer::<name>`` for the parts of the client step,
and the pass (forward / recompute / backward / update) read from JAX's own
markers in the same name stack.

Where the device spends its time is read from a profiler trace, not from a
cost model: this module gives each stage and each part a name that survives
into the compiled program (each op's ``op_name`` metadata) and from there
into the trace's op metadata, where ``benchmarks/xplane_meta.py`` +
``layer_metrics/stage_common.py`` / ``layer_common.py`` /
``pass_common.py`` and ``tools/roofline_report.py`` sum MEASURED device
time by stage, by part and by pass.

Mechanism: :func:`stage` and :func:`layer` wrap a code region in
``jax.named_scope`` with their prefix. Named scopes are **metadata only** —
they change neither the math nor XLA's optimization decisions (pinned by
tests/observability/test_stage_attribution.py and test_layer_scopes.py,
whose reference arm traces under :func:`disabled`; nothing else turns the
scopes off). Autodiff and ``vmap``/``scan`` transforms preserve the name
stack, so a scope's backward-pass ops carry the same names as its forward
ops.

The canonical stages (:data:`SPINE_STAGES`; an op belongs to the LAST
``fl_stage::`` of its name stack, :func:`stage_of`):

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
- ``evaluate``      — the evaluation round's vmapped client part
  (server/simulation.py ``eval_round``)

The parts of the client step (:data:`LAYER_SCOPES`; they nest, and an op
counts for EVERY ``fl_layer::`` its name stack holds, :func:`layers_of`).
The same names in all five model families, so one reader serves every
cell:

- ``embed`` / ``head`` — token (and position) embedding; pooling or the
  last-token gather and the classifier product
- ``attention``     — projections, the flash call or the dense scores,
  ``o_proj`` (models/transformer.py, models/jamba.py, models/nemotron_h.py)
- ``gqa_flash``     — inside ``attention``: the three flash calls over
  grouped key/value heads and what surrounds them (models/nemotron_h.py,
  and the full-attention layers of models/afmoe.py), as ``mla_flash`` is
  inside ``mla_attention``
- ``window_flash``  — inside ``attention``: the same three calls under a
  sliding window (``flash_attention(window=...)``) and what surrounds them,
  a sliding layer's in place of ``gqa_flash`` (models/afmoe.py)
- ``mla_attention`` ⊃ ``mla_flash`` — latent attention and its flash calls
  (models/deepseek.py)
- ``mamba_mixer`` ⊃ ``ssm_scan`` — the state-space mixer and its scan
  (models/jamba.py, kernels/selective_scan.py)
- ``ssd_mixer`` ⊃ ``ssd_scan`` — the scalar-decay (Mamba-2) mixer and its
  chunked recurrence alone, from the split of ``xBC`` to ``y`` before the
  gated norm: at widths on the 128-lane tiles the Mosaic calls
  ``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` with the running sums and their two
  layouts around them, else the ``jnp`` form (models/nemotron_h.py,
  kernels/ssd_scan.py)
- ``mlp``           — the dense feed-forward (GELU or SwiGLU)
- ``moe`` ⊃ ``moe_router``, ``moe_experts``; ``shared_experts`` beside it
  (models/deepseek.py, models/nemotron_h.py), disjoint from ``mlp``
- ``moe_latent``    — inside ``moe``: the two projections between the
  model's width and the latent the routed experts work in
  (models/nemotron_h.py)
- ``norm``          — LayerNorm / RMSNorm, wherever one lies (so it nests
  in ``mamba_mixer`` / ``mla_attention``)
- ``lora``          — an adapted projection's adapter branch only
  (models/decoder_common.py ``lora_dense``)
- ``optimizer``     — ``tx.update``, ``apply_updates`` and the step's
  padding selects (clients/engine.py)
- ``param_cast``    — the parameters cast to the compute type
  (precision/policy.py ``cast_model_def``)
- ``shared_cast``   — a shared base's matrices cast and stacked once a
  round (``bind_shared``)

The pass (:func:`pass_of`, for an op under ``local_train``): ``recompute``
if its name stack holds ``rematted_computation`` (what ``jax.checkpoint`` /
``nn.remat`` runs again on the way back), else ``backward`` if it holds
``transpose(``, else ``forward`` if it holds ``jvp(``, else ``update``
(optimizer, masks, meters). The markers are JAX's; a test pins them.
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
    "evaluate",
)

# The same for the parts of the client step. Every name here has a reader
# among BENCHMARK.json's per-layer metrics (a test holds the two lists to
# each other): a scope nothing reads is code.
LAYER_PREFIX = "fl_layer::"
LAYER_SCOPES = (
    "embed",
    "attention",
    "mla_attention",
    "mla_flash",
    "mamba_mixer",
    "ssm_scan",
    "ssd_mixer",
    "ssd_scan",
    "gqa_flash",
    "window_flash",
    "mlp",
    "moe",
    "moe_router",
    "moe_experts",
    "moe_latent",
    "shared_experts",
    "norm",
    "lora",
    "head",
    "optimizer",
    "param_cast",
    "shared_cast",
)

# What JAX itself writes into an op's name stack, in the order they decide
# the pass (tests/observability/test_layer_scopes.py pins them on compiled
# toy steps, so a JAX upgrade that renames one fails a test).
PASS_MARKERS = (("recompute", "rematted_computation"),
                ("backward", "transpose("),
                ("forward", "jvp("))
PASSES = ("forward", "recompute", "backward", "update")

_STAGE_RE = re.compile(re.escape(STAGE_PREFIX) + r"([A-Za-z0-9_.\-]+)")
_LAYER_RE = re.compile(re.escape(LAYER_PREFIX) + r"([A-Za-z0-9_.\-]+)")

_enabled = True


@contextlib.contextmanager
def disabled() -> Iterator[None]:
    """Trace without stage and layer scopes: the reference arm of the
    bit-identity tests, and nothing else."""
    global _enabled
    prev = _enabled
    _enabled = False
    try:
        yield
    finally:
        _enabled = prev


@contextlib.contextmanager
def _scope(name: str) -> Iterator[None]:
    """Named scopes are trace-time metadata: nothing runs for them. ``jax``
    is imported lazily so tools can import this module's parsing helpers
    without a backend."""
    if not _enabled:
        yield
        return
    import jax

    with jax.named_scope(name):
        yield


def stage(name: str):
    """Scope a traced code region as spine stage ``name``."""
    return _scope(STAGE_PREFIX + name)


def layer(name: str):
    """Scope a traced code region as part ``name`` of the client step."""
    return _scope(LAYER_PREFIX + name)


def stage_of(op_name: str | None) -> str | None:
    """The spine stage an HLO/trace ``op_name`` path belongs to, or None.

    Takes the LAST ``fl_stage::`` component on the path — scopes nest
    (``server_update`` wraps ``robust_aggregate`` wraps nothing), and the
    innermost name is the most specific attribution."""
    if not op_name:
        return None
    hits = _STAGE_RE.findall(op_name)
    return hits[-1] if hits else None


def layers_of(op_name: str | None) -> frozenset:
    """Every ``fl_layer::`` part on an ``op_name`` path: the scopes nest
    (the scan lies in the mixer, a norm in latent attention), and an op
    counts for each part that holds it."""
    return frozenset(_LAYER_RE.findall(op_name)) if op_name else frozenset()


def pass_of(op_name: str | None) -> str | None:
    """The pass of the client step an ``op_name`` path under
    ``fl_stage::local_train`` belongs to (one of :data:`PASSES`), or None
    for an op of no or another stage."""
    if stage_of(op_name) != "local_train":
        return None
    return next((name for name, marker in PASS_MARKERS if marker in op_name),
                "update")
