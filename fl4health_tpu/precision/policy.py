"""PrecisionConfig + the in-graph mechanics of mixed-precision training.

The recipe (Micikevicius et al., "Mixed Precision Training",
arXiv:1710.03740), engine-native:

- **compute dtype**: float params and float inputs are cast to
  ``compute_dtype`` at model-apply time — inside the compiled train step,
  via a shallow wrapper around the logic's :class:`ModelDef` — so the
  forward/backward runs on the MXU-native bf16/fp16 path for EVERY model,
  including ones with no ``dtype`` knob, and every client logic that
  routes its forward through ``logic.model.apply`` (the default
  ``predict``, the DP per-example path, APFL's dual forward, ...).
- **f32 master weights**: ``TrainState.params`` (and the optimizer state
  derived from it) stay f32. Gradients are taken with respect to the f32
  master — the cast's VJP promotes the cotangent back to f32 at the
  parameter boundary — and optax updates apply in f32. Penalty terms that
  read ``params`` directly (FedProx/Ditto prox, SCAFFOLD variates, DP
  clip+noise) therefore compute in f32, untouched by the policy.
- **loss scaling** (fp16): the backward pass is seeded with the scale as
  the loss cotangent (mathematically identical to scaling the loss, zero
  model edits), gradients are unscaled in f32, and a non-finite gradient
  skips the optimizer step. Scale / growth counter / skipped-step count
  live in the carried :class:`TrainState`, so the chunked-scan and
  pipelined execution modes evolve the scale identically.

The ONE promotion rule shared by the engine cast and both conv
implementations (``models/cnn.py`` ``nn.Conv`` / ``MxuConv``) is
:func:`conv_compute_dtype`: compute dtype = ``jnp.result_type`` over the
input and every parameter entering the op. Under the engine cast all of
them are already ``compute_dtype``, so the rule degenerates to the policy
dtype; without a policy it reproduces flax's ``dtype=None`` promotion.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from fl4health_tpu.observability import stages

_DTYPE_ALIASES = {
    "f32": "float32", "fp32": "float32", "float32": "float32",
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "f16": "float16", "fp16": "float16", "float16": "float16",
}
_LOSS_SCALE_MODES = ("auto", "none", "static", "dynamic")


def _canonical_dtype_name(dtype: Any) -> str:
    if isinstance(dtype, str):
        name = _DTYPE_ALIASES.get(dtype.lower())
        if name is None:
            raise ValueError(
                f"compute_dtype must be one of f32|bf16|fp16 (got {dtype!r})"
            )
        return name
    name = jnp.dtype(dtype).name
    if name not in _DTYPE_ALIASES:
        raise ValueError(
            f"compute_dtype must be float32, bfloat16 or float16; got {name}"
        )
    return name


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Static mixed-precision recipe for the cohort engine.

    - ``compute_dtype``: dtype of the forward/backward math
      (``"f32"``/``"bf16"``/``"fp16"`` or the jnp dtypes). ``f32`` builds
      the exact pre-precision program (bit-identical, pinned by tests).
    - ``keep_master_f32``: the master-weight contract. Only ``True`` is
      supported for low-precision compute — params, optimizer state, DP
      noise, EF residuals and ZeRO-1 server shards all assume f32 master
      state; ``False`` is accepted solely for the no-op f32 config.
    - ``loss_scale``: ``"none"`` | ``"static"`` | ``"dynamic"``; the
      default ``"auto"`` resolves to ``"dynamic"`` for fp16 (whose 5-bit
      exponent underflows real gradients) and ``"none"`` otherwise.
    - ``init_scale``/``growth_interval``/``growth_factor``/
      ``backoff_factor``/``min_scale``/``max_scale``: the standard dynamic
      scaler knobs (torch.cuda.amp semantics, evolved per local step).
    """

    compute_dtype: Any = "bfloat16"
    keep_master_f32: bool = True
    loss_scale: str = "auto"
    init_scale: float = 2.0 ** 15
    growth_interval: int = 200
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    min_scale: float = 1.0
    max_scale: float = 2.0 ** 24

    def __post_init__(self):
        name = _canonical_dtype_name(self.compute_dtype)
        object.__setattr__(self, "compute_dtype", name)
        if self.loss_scale not in _LOSS_SCALE_MODES:
            raise ValueError(
                f"loss_scale must be one of {_LOSS_SCALE_MODES}; "
                f"got {self.loss_scale!r}"
            )
        if name == "float32" and self.loss_scale in ("static", "dynamic"):
            raise ValueError(
                "loss_scale with f32 compute is a no-op that still pays the "
                "finite-check and skip machinery — pick a low-precision "
                "compute_dtype or loss_scale='none'"
            )
        if not self.keep_master_f32 and name != "float32":
            raise ValueError(
                "keep_master_f32=False is unsupported for low-precision "
                "compute: the engine's TrainState, DP clip->noise, EF "
                "residuals and ZeRO-1 server shards are all contracted to "
                "f32 master weights (Micikevicius et al.'s recipe). Use "
                "the per-model dtype knob if you truly want low-precision "
                "storage."
            )
        if self.init_scale <= 0 or self.min_scale <= 0:
            raise ValueError("loss scales must be positive")
        if self.growth_interval < 1:
            raise ValueError("growth_interval must be >= 1")
        if self.growth_factor <= 1.0 or not (0.0 < self.backoff_factor < 1.0):
            raise ValueError(
                "growth_factor must exceed 1.0 and backoff_factor lie in "
                "(0, 1) — otherwise the dynamic scale cannot move the right "
                "direction"
            )

    # -- derived facts ---------------------------------------------------
    @property
    def compute_dtype_name(self) -> str:
        return self.compute_dtype  # canonicalized in __post_init__

    @property
    def compute_jnp_dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def casts_compute(self) -> bool:
        return self.compute_dtype != "float32"

    @property
    def resolved_loss_scale(self) -> str:
        if self.loss_scale != "auto":
            return self.loss_scale
        return "dynamic" if self.compute_dtype == "float16" else "none"

    @property
    def scaling_active(self) -> bool:
        return self.resolved_loss_scale != "none"

    @property
    def active(self) -> bool:
        """False == the engine builds the exact pre-precision program."""
        return self.casts_compute or self.scaling_active

    def describe(self) -> dict:
        """JSON-able policy facts (run manifest / round+program events /
        bench artifacts)."""
        return {
            "compute_dtype": self.compute_dtype_name,
            "keep_master_f32": self.keep_master_f32,
            "loss_scale": self.resolved_loss_scale,
        }


def resolve(precision: PrecisionConfig | None) -> PrecisionConfig | None:
    """None-or-inactive -> None, so every consumer has ONE check for "build
    the legacy program"."""
    if precision is None or not precision.active:
        return None
    return precision


# ---------------------------------------------------------------------------
# Casting
# ---------------------------------------------------------------------------

def cast_floats(tree: Any, dtype) -> Any:
    """Cast every floating leaf of a pytree to ``dtype``; integer/bool
    leaves (labels, token ids, masks) pass through untouched."""
    dtype = jnp.dtype(dtype)

    def cast(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.floating):
            return jnp.asarray(x).astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def conv_compute_dtype(x_dtype, *param_dtypes):
    """THE shared promotion rule for ``dtype=None`` ops: compute dtype =
    ``jnp.result_type`` over the input and every parameter entering the op
    (flax's ``promote_dtype`` semantics — bias included). Both conv impls
    (``nn.Conv``, ``MxuConv``) and the engine-side cast agree on this rule,
    so their bf16 outputs are interchangeable."""
    return jnp.result_type(x_dtype, *param_dtypes)


def cast_model_def(model_def: Any, compute_dtype) -> Any:
    """Wrap a :class:`ModelDef`'s ``apply`` to cast float params AND float
    inputs to the compute dtype on TRAIN calls only.

    Casting both sides matters: under flax's ``dtype=None`` promotion
    (``conv_compute_dtype``) a bf16 kernel against an f32 input would
    promote straight back to f32 compute. Eval (``train=False``) runs on
    the f32 master untouched, so checkpoint/early-stop selection scores the
    weights that actually ship. ``model_state`` (batch stats etc.) stays
    f32 — norm statistics in low precision drift, and the promotion rule
    simply computes those ops in f32.
    """
    compute_dtype = jnp.dtype(compute_dtype)
    inner_apply = model_def.apply

    def apply(params, model_state, x, train=True, rng=None, **kwargs):
        if train:
            with stages.layer("param_cast"):
                params = cast_floats(params, compute_dtype)
            x = cast_floats(x, compute_dtype)
        return inner_apply(params, model_state, x, train=train, rng=rng,
                           **kwargs)

    return dataclasses.replace(model_def, apply=apply)


def wrap_logic_compute(logic: Any, compute_dtype) -> Any:
    """Shallow-copy a ClientLogic with its ``model`` apply cast-wrapped.

    The copy keeps the logic's class (so trace-time introspection like the
    ZeRO-2 ``value_and_grads``-override check still sees the real type) and
    every algorithm attribute; only the ``ModelDef`` is replaced. Logics
    that forward through something other than ``self.model`` (custom
    ensembles) simply keep computing in f32 — the policy degrades to a
    no-op there, never to wrong numerics."""
    wrapped = copy.copy(logic)
    wrapped.model = cast_model_def(logic.model, compute_dtype)
    return wrapped


# ---------------------------------------------------------------------------
# Dynamic loss scaling (in-graph; state carried in TrainState.loss_scale)
# ---------------------------------------------------------------------------

def loss_scale_init(precision: PrecisionConfig | None) -> dict | None:
    """The per-client loss-scale pytree carried in ``TrainState``:
    ``{"scale", "growth", "skipped"}``. None when the policy needs no
    scaling — the TrainState keeps its legacy structure (``None`` is an
    empty pytree node), so precision-off checkpoints/programs are
    unchanged."""
    precision = resolve(precision)
    if precision is None or not precision.scaling_active:
        return None
    return {
        "scale": jnp.asarray(precision.init_scale, jnp.float32),
        "growth": jnp.zeros((), jnp.int32),
        "skipped": jnp.zeros((), jnp.float32),
    }


def tree_all_finite(tree: Any) -> jax.Array:
    """1.0 when every floating entry of the pytree is finite, else 0.0 —
    the skip predicate of the dynamic scaler (f32 scalar so it can gate
    ``_mask_tree`` selections directly)."""
    checks = [
        jnp.all(jnp.isfinite(leaf))
        for leaf in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(leaf.dtype, jnp.inexact)
    ]
    if not checks:
        return jnp.ones((), jnp.float32)
    return jnp.stack(checks).all().astype(jnp.float32)


def loss_scale_step(ls: dict, finite: jax.Array,
                    precision: PrecisionConfig) -> dict:
    """One scaler update (torch.cuda.amp semantics, jit-traceable):
    non-finite gradients back the scale off and zero the growth streak;
    ``growth_interval`` consecutive finite steps double it (clamped to
    [min_scale, max_scale]). ``skipped`` counts skipped optimizer steps —
    the telemetry/round-event ``loss_scale_skips`` statistic. A static
    scale skips and counts identically but never moves."""
    ok = finite > 0
    skipped = ls["skipped"] + (1.0 - finite)
    if precision.resolved_loss_scale == "static":
        return {"scale": ls["scale"], "growth": ls["growth"],
                "skipped": skipped}
    grown = ls["growth"] + 1
    do_grow = grown >= precision.growth_interval
    new_scale = jnp.where(
        ok,
        jnp.where(
            do_grow,
            jnp.minimum(ls["scale"] * precision.growth_factor,
                        precision.max_scale),
            ls["scale"],
        ),
        jnp.maximum(ls["scale"] * precision.backoff_factor,
                    precision.min_scale),
    )
    new_growth = jnp.where(ok, jnp.where(do_grow, 0, grown), 0)
    return {"scale": new_scale, "growth": new_growth, "skipped": skipped}
