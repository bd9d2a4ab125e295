"""Client training engine — the reference's BasicClient loop, TPU-native.

Reference behavior (/root/reference/fl4health/clients/basic_client.py):
``train_by_epochs``/``train_by_steps`` (:627,:699) iterate a DataLoader in
eager PyTorch: train_step = zero_grad -> predict -> loss -> backward ->
transform_gradients -> step (:578-605), with hook methods before/after
steps/epochs (:1233-1302), loss meters + metric managers, and ``validate``
(:867) running val + optional test loaders.

TPU-native design: one local-training phase is ONE compiled program —
``lax.scan`` over a statically-shaped stack of batches. Heterogeneous client
data sizes are handled by padding to the cohort max with per-step and
per-example masks (empty-batch semantics of basic_client.py:660-662 become
mask arithmetic). Algorithm variants plug in as pure functions on a
``ClientLogic`` object; persistent aux state (control variates, personal
models) rides in ``TrainState.extra`` and is vmappable across the clients
axis, so N simulated clients train as one SPMD program.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from fl4health_tpu.core.pytree import merge_trees, split_by_path, tree_nbytes
from fl4health_tpu.core.types import Params, PRNGKey, PyTree
from fl4health_tpu.losses.containers import LossMeter
from fl4health_tpu.precision import policy as precision_policy
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.observability import stages as stage_attr
from fl4health_tpu.observability.registry import get_registry
from fl4health_tpu.observability.spans import get_tracer


# ---------------------------------------------------------------------------
# Data containers
# ---------------------------------------------------------------------------

@struct.dataclass
class Batch:
    """One step's data. Leading [steps] axis when stacked for scan.

    example_mask: [B] validity (ragged final batch -> zeros); step_mask: scalar
    0/1 (padding steps beyond a client's true data length are full no-ops).
    """

    x: jax.Array
    y: jax.Array
    example_mask: jax.Array
    step_mask: jax.Array


@struct.dataclass
class TrainState:
    """Scan carry for local training."""

    params: Params
    opt_state: Any
    model_state: Any  # mutable collections (batch_stats); empty dict if none
    rng: PRNGKey
    step: jax.Array
    extra: Any = None  # algorithm-specific persistent state
    # dynamic loss-scale state ({"scale", "growth", "skipped"}) when the
    # precision policy scales (fp16); None otherwise — an empty pytree
    # node, so precision-off states keep their legacy structure exactly
    loss_scale: Any = None


@struct.dataclass
class StepOutput:
    losses: Any  # dict of scalars (backward + additional)
    preds: jax.Array
    targets: jax.Array
    example_mask: jax.Array
    step_mask: jax.Array
    # global norm of the post-transform_gradients gradient — populated only
    # when the train maker was built with collect_telemetry=True (None is an
    # empty pytree node, so the default costs nothing)
    grad_norm: Any = None


# ---------------------------------------------------------------------------
# Model definition — framework-agnostic adapter (flax, haiku, hand-rolled)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelDef:
    """init(rng, sample_x) -> (params, model_state)
    apply(params, model_state, x, train, rng) -> ((preds, features), model_state)

    ``preds`` is a dict with at least key "prediction"; ``features`` is a dict
    of intermediate activations (reference predict() contract,
    basic_client.py:992).

    ``per_client`` is THE predicate over dotted parameter paths that says
    which leaves every client holds a copy of (trains, exchanges, has
    optimizer state for). ``None`` means every leaf. Leaves outside it are
    *shared*: they exist once (in the server state), reach the vmapped
    client step unbatched and are never pulled, pushed, averaged or
    updated (a frozen base under per-client adapters). A model with the
    predicate also gives ``bind_shared(shared) -> apply``: the forward over
    the per-client leaves alone (``apply``'s signature and results), closed
    over the shared tree. The round program calls it once a round, outside
    the client vmap and the local-step scan, so whatever it does to the
    shared leaves before the forward (the cast of a base's matrices to the
    compute type) happens once. :func:`merged_forward` is the plain form.

    ``build_gauges(batch_shape, n_clients) -> {name: number}``, if the model
    has one, gives static facts of its layers (a routed layer's experts held
    and its row bound) that the simulation reports once at build, beside the
    split's own counts.
    """

    init: Callable[[PRNGKey, jax.Array], tuple[Params, Any]]
    apply: Callable[..., tuple[tuple[dict, dict], Any]]
    per_client: Callable[[str], bool] | None = None
    bind_shared: Callable[[Params], Callable[..., Any]] | None = None
    build_gauges: Callable[[tuple, int], dict] | None = None


def merged_forward(apply):
    """The plain ``ModelDef.bind_shared``: ``apply`` over the union of the
    shared and the per-client leaves."""
    def bind(shared):
        return lambda params, model_state, x, **kwargs: apply(
            merge_trees(shared, params), model_state, x, **kwargs)
    return bind


def from_flax(module, mutable: tuple[str, ...] = ("batch_stats",)) -> ModelDef:
    """Wrap a flax.linen module whose __call__ returns either an array or a
    (preds_dict, features_dict) pair. A module that defines
    ``per_client_param(path) -> bool`` brings its own split of the parameters
    into per-client and shared leaves (see :class:`ModelDef`); its
    ``bind_shared(shared) -> (params, x) -> (preds, features)``, if it has
    one, is the forward over the two halves (such a module has no mutable
    collections), else the halves are merged and ``__call__`` runs."""
    per_client = getattr(module, "per_client_param", None)

    def init(rng, sample_x):
        variables = module.init(
            {"params": rng, "dropout": rng, "mask": rng, "sampling": rng},
            sample_x, train=False,
        )
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return params, model_state

    def apply(params, model_state, x, train=True, rng=None, **kwargs):
        # Extra kwargs (e.g. APFL's alpha, GPFL's conditional inputs) are
        # forwarded to the module so algorithm-specific forwards don't need
        # their own adapter. The extra rng streams serve masked layers
        # ("mask", models/masked.py) and VAE reparameterization ("sampling").
        variables = {"params": params, **(model_state or {})}
        # Stochastic streams only while training: eval uses the masked
        # layers' deterministic expectation and the VAEs' fixed noise so
        # repeated validation of identical params agrees (checkpoint/early-
        # stop selection must not ride sampling noise).
        rngs = {}
        if rng is not None:
            rngs["dropout"] = rng
            if train:
                rngs["mask"] = jax.random.fold_in(rng, 1)
                rngs["sampling"] = jax.random.fold_in(rng, 2)
        if train and model_state:
            out, new_state = module.apply(
                variables, x, train=True, rngs=rngs,
                mutable=list(model_state.keys()), **kwargs
            )
        else:
            out = module.apply(variables, x, train=train, rngs=rngs, **kwargs)
            new_state = model_state
        if isinstance(out, tuple):
            preds, features = out
        else:
            preds, features = {"prediction": out}, {}
        return (preds, features), new_state

    bind = None
    if per_client is not None:
        bind = merged_forward(apply)
        if hasattr(module, "bind_shared"):
            def bind(shared):
                forward = module.bind_shared(shared)
                return lambda params, model_state, x, **kwargs: (
                    forward(params, x), model_state)
    return ModelDef(init=init, apply=apply, per_client=per_client,
                    bind_shared=bind,
                    build_gauges=getattr(module, "build_gauges", None))


def bind_shared(logic: "ClientLogic", shared: Params) -> "ClientLogic":
    """Shallow-copy a ClientLogic over ``shared``: the clients' own
    ``params`` hold the per-client leaves only, and ``shared`` (traced once,
    outside the client vmap) fills in the rest. Gradients are taken with
    respect to ``params`` alone, so the shared leaves get neither gradient
    nor optimizer state."""
    bound = copy.copy(logic)
    bound.model = dataclasses.replace(
        logic.model, apply=logic.model.bind_shared(shared))
    return bound


def init_split(model: ModelDef, rng: PRNGKey, sample_x):
    """``model.init`` for a model with shared leaves, without ever holding
    the shared leaves: returns (per-client params, model_state, the shared
    tree as ``ShapeDtypeStruct``s, ``make_shared() -> shared tree``). Both
    halves are the values ``model.init(rng, sample_x)`` would give (to the
    rounding of a jitted sampler); each is computed under ``jit`` so the
    other half is dead code."""
    def half(which):
        def f(r):
            params, model_state = model.init(r, sample_x)
            return split_by_path(params, model.per_client)[which], model_state
        return f

    per_client, model_state = jax.jit(half(0))(rng)
    shared_abstract, _ = jax.eval_shape(half(1), rng)
    return (per_client, model_state, shared_abstract,
            lambda: jax.jit(half(1))(rng)[0])


# ---------------------------------------------------------------------------
# Client logic — the algorithm plug-in surface
# ---------------------------------------------------------------------------

class ClientLogic:
    """Pure-function hook surface mirroring BasicClient's override points.

    Subclasses override any of these; all must stay jit-traceable. ``ctx`` is
    the per-round context (e.g. snapshot of the received global params, the
    drift penalty weight) built once per round by ``init_round_context``.
    """

    def __init__(self, model: ModelDef, criterion: Callable):
        self.model = model
        self.criterion = criterion  # (preds_array, targets, example_mask) -> scalar

    # -- round lifecycle ----------------------------------------------------
    def init_extra(self, params: Params) -> Any:
        """Persistent algorithm state created at client setup (round 1)."""
        return None

    def init_round_context(self, state: TrainState, server_payload: Any) -> Any:
        """Per-round constants (update_before_train, basic_client.py:1233)."""
        return None

    def finalize_round(self, state: TrainState, ctx: Any, local_steps: jax.Array) -> TrainState:
        """update_after_train (basic_client.py:1248) — e.g. SCAFFOLD variates."""
        return state

    # -- step ---------------------------------------------------------------
    def predict(self, params, model_state, batch: Batch, rng, train: bool,
                extra=None, ctx=None):
        """(basic_client.py:992). ``extra`` is the persistent algorithm state
        (e.g. APFL's alpha); ``ctx`` the per-round context (e.g. GPFL's frozen
        conditional inputs) for logics whose forward depends on them."""
        del extra, ctx
        return self.model.apply(params, model_state, batch.x, train=train, rng=rng)

    def training_loss(
        self, preds: dict, features: dict, batch: Batch, params: Params,
        state: TrainState, ctx: Any,
    ) -> tuple[jax.Array, dict]:
        """-> (backward_loss, additional dict) (compute_training_loss :1054)."""
        loss = self.criterion(preds["prediction"], batch.y, batch.example_mask)
        return loss, {}

    def eval_loss(
        self, preds: dict, features: dict, batch: Batch, params: Params,
        state: TrainState, ctx: Any,
    ) -> tuple[jax.Array, dict]:
        loss = self.criterion(preds["prediction"], batch.y, batch.example_mask)
        return loss, {}

    def transform_gradients(self, grads: Params, state: TrainState, ctx: Any) -> Params:
        """(basic_client.py:1294) — e.g. SCAFFOLD variate correction."""
        return grads

    def augment(self, batch: Batch, rng: PRNGKey, ctx: Any) -> Batch:
        """Per-step train-time data augmentation (the role of the reference's
        dataloader-side transform pipelines, e.g. nnunetv2's augmenters behind
        nnunet_utils.py:307). Runs inside the compiled scan, train only; the
        key is folded from the step key so the default identity leaves every
        existing RNG stream untouched."""
        del rng, ctx
        return batch

    def update_before_step(self, state: TrainState, ctx: Any, batch: Batch) -> TrainState:
        """(basic_client.py:1260 update_before_step) — runs before the
        gradient step; e.g. DeepMMD kernel training on the incoming batch.
        The engine masks this hook's state changes on padding steps
        (``batch.step_mask == 0``), but implementations should still gate
        expensive work on the mask to avoid wasted compute."""
        return state

    def _loss_fn(self, state: TrainState, ctx: Any, batch: Batch,
                 step_rng: PRNGKey):
        """The differentiated closure params -> (backward, (preds,
        additional, new_model_state)). ONE definition shared by the default
        ``value_and_grads`` below and the engine's fp16 loss-scaling path
        (which seeds its backward via ``jax.vjp``), so the scaled and
        unscaled gradient paths cannot silently drift apart."""

        def loss_fn(params):
            (preds, features), new_model_state = self.predict(
                params, state.model_state, batch, step_rng, train=True,
                extra=state.extra, ctx=ctx,
            )
            backward, additional = self.training_loss(
                preds, features, batch, params, state, ctx
            )
            return backward, (preds, additional, new_model_state)

        return loss_fn

    def value_and_grads(self, state: TrainState, ctx: Any, batch: Batch, step_rng: PRNGKey):
        """Compute ((backward, (preds, additional, new_model_state)), grads).

        Default: whole-batch ``value_and_grad``. DP logics override this with
        vmapped per-example gradients + clip + noise (the Opacus hook point,
        instance_level_dp_client.py:85-114 in the reference)."""
        return jax.value_and_grad(
            self._loss_fn(state, ctx, batch, step_rng), has_aux=True
        )(state.params)

    def update_after_step(self, state: TrainState, ctx: Any, batch: Batch,
                          preds: dict | None = None) -> TrainState:
        """(basic_client.py:1272) — e.g. APFL alpha update. ``preds`` is the
        step's prediction dict so hooks can reuse it without re-running the
        model."""
        return state

    # -- wire ---------------------------------------------------------------
    def pack(self, state: TrainState, pushed_params: Params, train_losses: dict) -> Any:
        """Build the packet sent to the server (get_parameters + packer,
        basic_client.py:153). Default: just the exchanged params."""
        return pushed_params


# ---------------------------------------------------------------------------
# Criteria
# ---------------------------------------------------------------------------

def masked_cross_entropy(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean CE over valid examples; integer or one-hot targets."""
    if targets.ndim == logits.ndim:
        log_p = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.sum(targets * log_p, axis=-1)
    else:
        per = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    m = mask.astype(jnp.float32)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


def masked_mse(preds: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    per = jnp.mean(
        jnp.square(preds - targets).reshape(preds.shape[0], -1), axis=-1
    )
    m = mask.astype(jnp.float32)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


def masked_bce_with_logits(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    logits = logits.reshape(logits.shape[0], -1)
    targets = targets.reshape(targets.shape[0], -1).astype(jnp.float32)
    per = jnp.mean(optax.sigmoid_binary_cross_entropy(logits, targets), axis=-1)
    m = mask.astype(jnp.float32)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


# ---------------------------------------------------------------------------
# Engine: compiled train / eval phases
# ---------------------------------------------------------------------------

def create_train_state(
    logic: ClientLogic, tx: optax.GradientTransformation, rng: PRNGKey,
    sample_x: jax.Array,
    precision: Any = None,
    init: tuple[Params, Any] | None = None,
) -> TrainState:
    """``precision`` (a PrecisionConfig, optional): params/opt state are
    ALWAYS created f32 master (init runs in the model's native dtypes); a
    scaling policy additionally seeds the carried loss-scale state.
    ``init``: the first two results of :func:`init_split`, for a caller that
    needs the other two as well."""
    if logic.model.per_client is not None:
        # the client's own leaves only; the shared ones are the caller's
        # (server/simulation.py holds them once, in the server state)
        params, model_state = (
            init_split(logic.model, rng, sample_x)[:2] if init is None
            else init)
    else:
        params, model_state = logic.model.init(rng, sample_x)
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        model_state=model_state,
        rng=rng,
        step=jnp.zeros((), jnp.int32),
        extra=logic.init_extra(params),
        loss_scale=precision_policy.loss_scale_init(precision),
    )


def _mask_tree(new: PyTree, old: PyTree, keep_new: jax.Array) -> PyTree:
    """Select new where keep_new==1 (real step) else old (padding no-op)."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(keep_new > 0, n, o), new, old)


def _microbatched_value_and_grads(logic, tx, state, ctx, batch, step_rng):
    """ZeRO-2 gradient path: split the batch into ``tx.n_shards``
    microbatches, compute per-microbatch grads, and hand the UNREDUCED
    [n_shards]-leading stack to ``tx.update`` — its psum_scatter does the
    reduction without ever materializing the summed gradient
    (parallel/zero.py Zero2ShardedOptimizer; the DeepSpeed-zero2 role of the
    reference's fedllm example).

    Exactness contract: each microbatch grad is pre-scaled by
    ``n * M_k / M_total`` (M_k = valid examples in microbatch k) so the
    optimizer's uniform mean reproduces the full-batch masked-mean gradient
    bit-for-math. This is exact for losses that are masked example-means
    plus state-only penalty terms (CE/MSE, FedProx/Ditto/MR-MTL penalties —
    weights sum to 1) and for affine transform_gradients hooks (SCAFFOLD's
    variate correction). Batch-coupled losses (contrastive normalizers over
    the whole batch) and non-affine gradient transforms (DP clipping) change
    semantics under microbatching — same caveat as any grad-accumulation
    scheme — and mutable model_state (batch stats) takes the LAST
    microbatch's update.
    """
    n = tx.n_shards
    b = batch.example_mask.shape[0]
    if b % n != 0:
        raise ValueError(
            f"ZeRO-2 engine path needs batch size divisible by n_shards: "
            f"batch={b}, n_shards={n}"
        )
    m = b // n

    def split(leaf):
        return leaf.reshape((n, m) + leaf.shape[1:])

    micro = Batch(
        x=jax.tree_util.tree_map(split, batch.x),
        y=jax.tree_util.tree_map(split, batch.y),
        example_mask=split(batch.example_mask),
        step_mask=jnp.broadcast_to(batch.step_mask, (n,)),
    )

    def one(mb, rng_k):
        (bw, aux), g = logic.value_and_grads(state, ctx, mb, rng_k)
        g = logic.transform_gradients(g, state, ctx)
        return (bw, aux), g

    # independent rng per microbatch: a shared key would draw IDENTICAL
    # dropout masks in every microbatch (correlated noise); per-fold keys
    # match grad-accumulation convention (still a different stream than the
    # full-batch draw — stochastic layers are approximate here, like the
    # other microbatching caveats above)
    rngs = jax.vmap(lambda i: jax.random.fold_in(step_rng, i))(jnp.arange(n))
    (bw_k, (preds_k, add_k, mstate_k)), grads_k = jax.vmap(one)(micro, rngs)

    m_k = jnp.sum(micro.example_mask.astype(jnp.float32), axis=1)  # [n]
    m_tot = jnp.maximum(jnp.sum(m_k), 1.0)
    w = n * m_k / m_tot  # uniform mean of w_k·g_k == masked-mean grad
    grads_scaled = jax.tree_util.tree_map(
        lambda g: g * w.reshape((n,) + (1,) * (g.ndim - 1)), grads_k
    )
    recombine = lambda v: jnp.sum((w / n) * v)  # noqa: E731 — Σ (M_k/M_tot)·v_k
    backward = recombine(bw_k)
    additional = {k: recombine(v) for k, v in add_k.items()}
    preds = jax.tree_util.tree_map(
        lambda p: p.reshape((b,) + p.shape[2:]), preds_k
    )
    new_model_state = jax.tree_util.tree_map(lambda s: s[-1], mstate_k)
    return backward, preds, additional, new_model_state, grads_scaled


def make_train_step(logic: ClientLogic, tx: optax.GradientTransformation,
                    collect_telemetry: bool = False, precision: Any = None):
    """Returns step(state, ctx, batch) -> (state, StepOutput) — jit/scan-safe.

    ``collect_telemetry`` additionally populates ``StepOutput.grad_norm``
    with the global norm of the gradient AFTER ``transform_gradients`` (what
    the optimizer actually consumes — SCAFFOLD correction, DP noise etc.
    included). A pure extra output: the parameter update math is untouched,
    so telemetry-on trajectories stay bit-identical to telemetry-off
    (tests/observability/test_telemetry.py).

    ``precision`` (a :class:`~fl4health_tpu.precision.PrecisionConfig`, or
    None): the engine-level mixed-precision policy. With a low-precision
    compute dtype the logic's model apply is wrapped so float params AND
    float inputs are cast at apply time — the forward/backward runs in
    bf16/fp16 for EVERY logic routing through ``logic.model`` (the default
    path, DP per-example gradients, dual forwards) while gradients come
    back f32 at the parameter boundary (the cast's VJP) and optax applies
    them to the f32 master weights. fp16 adds in-graph loss scaling: the
    backward is seeded with the scale as the loss cotangent, gradients are
    unscaled in f32, a non-finite gradient skips the step (params,
    optimizer and model_state untouched) and the scale/growth/skip state
    evolves in ``TrainState.loss_scale``. ``None`` (or an inactive config)
    builds the exact legacy step — bit-identical, pinned by
    tests/precision/."""
    precision = precision_policy.resolve(precision)
    if precision is not None and precision.casts_compute:
        logic = precision_policy.wrap_logic_compute(
            logic, precision.compute_jnp_dtype
        )
    scaling = precision is not None and precision.scaling_active
    unreduced = getattr(tx, "expects_unreduced_grads", False)
    if scaling:
        if unreduced:
            raise ValueError(
                "loss scaling cannot compose with the ZeRO-2 microbatched "
                "gradient path (expects_unreduced_grads): the per-microbatch "
                "finite screen would skip shards independently and the "
                "pre-scaled recombination no longer holds — use bf16 (no "
                "scaling) with ZeRO-2"
            )
        if type(logic).value_and_grads is not ClientLogic.value_and_grads:
            # A logic that owns its gradient computation (DP per-example
            # clip+noise) would see SCALED gradients inside its mechanism —
            # the clip bound and noise sigma would silently mis-calibrate.
            # bf16 (range of f32, no scaling needed) composes fine.
            raise TypeError(
                f"in-graph loss scaling wraps the engine's default gradient "
                f"path only: {type(logic).__name__} overrides "
                "value_and_grads (e.g. DP per-example gradients), whose "
                "clip/noise calibration breaks under a scaled backward — "
                "use compute_dtype='bfloat16' with loss_scale='none'"
            )
    if unreduced:
        # The microbatch pre-scaling assumes the optimizer's uniform MEAN
        # reduction; a reduce="sum" ZeRO-2 would silently apply n_shards x
        # the true gradient (an effective-LR inflation).
        if getattr(tx, "reduce", "mean") != "mean":
            raise ValueError(
                "expects_unreduced_grads optimizers must use reduce='mean' "
                f"through the engine (got {tx.reduce!r}) — the microbatch "
                "weighting is calibrated for a uniform mean"
            )
        # A logic that overrides the gradient computation itself (DP
        # per-example clip+noise) would run it once PER MICROBATCH — noise
        # drawn n times and recombined no longer matches the (eps, delta)
        # accounting. Same loud-error policy as personalized.py.
        if type(logic).value_and_grads is not ClientLogic.value_and_grads:
            raise TypeError(
                f"ZeRO-2 microbatching cannot wrap {type(logic).__name__}: "
                "it overrides value_and_grads (e.g. DP per-example "
                "gradients), whose semantics change under microbatching"
            )

    def step(state: TrainState, ctx: Any, batch: Batch):
        # ``fl_layer::optimizer``: the update itself and the selects that
        # make a padding step a no-op, every one a pass over the state
        before = logic.update_before_step(state, ctx, batch)
        with stage_attr.layer("optimizer"):
            state = _mask_tree(before, state, batch.step_mask)
        rng, step_rng = jax.random.split(state.rng)
        batch = logic.augment(batch, jax.random.fold_in(step_rng, 0xA6), ctx)
        finite = None
        if unreduced:
            backward, preds, additional, new_model_state, grads = (
                _microbatched_value_and_grads(
                    logic, tx, state, ctx, batch, step_rng
                )
            )
        elif scaling:
            ls = state.loss_scale
            if ls is None:
                raise ValueError(
                    "loss scaling needs the carried scaler state: build the "
                    "TrainState with create_train_state(..., precision=...) "
                    "(FederatedSimulation(precision=...) does this)"
                )

            # THE default-path loss closure (logic._loss_fn — one shared
            # definition), driven through jax.vjp so the backward can be
            # SEEDED with the scale as the loss cotangent — mathematically
            # identical to scaling the loss (gradients are linear in the
            # cotangent) but it reaches every intermediate fp16 cotangent,
            # which is where the underflow lives. The primal loss stays
            # unscaled, so meters/telemetry report true values.
            backward, vjp_fn, (preds, additional, new_model_state) = jax.vjp(
                logic._loss_fn(state, ctx, batch, step_rng),
                state.params, has_aux=True,
            )
            grads = vjp_fn(ls["scale"].astype(backward.dtype))[0]
            # unscale in f32 (grads are f32 at the master-param boundary);
            # the finite screen runs on the UNSCALED gradient so a huge
            # scale can't masquerade as overflow
            inv = 1.0 / ls["scale"]
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            finite = precision_policy.tree_all_finite(grads)
            grads = logic.transform_gradients(grads, state, ctx)
        else:
            (backward, (preds, additional, new_model_state)), grads = (
                logic.value_and_grads(state, ctx, batch, step_rng)
            )
            grads = logic.transform_gradients(grads, state, ctx)
        keep = batch.step_mask  # padding steps must not move anything
        with stage_attr.layer("optimizer"):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            # a non-finite scaled gradient additionally skips the optimizer
            # step (master weights, optimizer state and batch stats untouched)
            keep_update = keep if finite is None else keep * finite
            new_state = state.replace(
                params=_mask_tree(new_params, state.params, keep_update),
                opt_state=_mask_tree(
                    new_opt_state, state.opt_state, keep_update),
                model_state=_mask_tree(
                    new_model_state, state.model_state, keep_update
                ),
                rng=rng,
                step=state.step + keep_update.astype(jnp.int32),
            )
        if scaling:
            # scaler state advances on REAL steps only (padding steps are
            # full no-ops); it advances on skipped steps too — that is how
            # the scale backs off and recovers
            new_ls = precision_policy.loss_scale_step(
                state.loss_scale, finite, precision
            )
            new_state = new_state.replace(
                loss_scale=_mask_tree(new_ls, state.loss_scale, keep)
            )
        new_state = logic.update_after_step(new_state, ctx, batch, preds=preds)
        grad_norm = None
        if collect_telemetry:
            if unreduced:
                # ZeRO-2 hands the optimizer an UNREDUCED [n_shards] stack;
                # the true gradient is its uniform mean (the pre-scaling is
                # calibrated for exactly that reduction)
                grad_norm = optax.global_norm(
                    jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
                )
            else:
                grad_norm = optax.global_norm(grads)
        out = StepOutput(
            losses={"backward": backward, **additional},
            preds=preds["prediction"],
            targets=batch.y,
            example_mask=batch.example_mask * keep,
            step_mask=keep,
            grad_norm=grad_norm,
        )
        return new_state, out

    return step


# -- in-scan telemetry accumulation (observability/telemetry.py consumers) --

def telemetry_acc_init() -> dict:
    """Scan-carry accumulator for per-client loss min/max + grad-norm
    statistics. NaN losses propagate through min/max by design — a poisoned
    step must surface in the telemetry, not be filtered out of it."""
    inf = jnp.asarray(jnp.inf, jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return {"loss_min": inf, "loss_max": -inf, "gn_sum": zero, "gn_max": zero}


def telemetry_acc_update(acc: dict, out: StepOutput) -> dict:
    loss = jnp.asarray(out.losses["backward"], jnp.float32)
    gn = jnp.asarray(out.grad_norm, jnp.float32)
    live = out.step_mask > 0  # padding steps must not move the stats
    return {
        "loss_min": jnp.minimum(acc["loss_min"], jnp.where(live, loss, jnp.inf)),
        "loss_max": jnp.maximum(acc["loss_max"], jnp.where(live, loss, -jnp.inf)),
        "gn_sum": acc["gn_sum"] + jnp.where(live, gn, 0.0),
        "gn_max": jnp.maximum(acc["gn_max"], jnp.where(live, gn, 0.0)),
    }


def telemetry_acc_finalize(acc: dict, n_steps: jax.Array) -> dict:
    """-> the engine's share of a RoundTelemetry row. A client that executed
    zero steps reports NaN (not the init sentinels)."""
    ran = n_steps > 0
    nan = jnp.asarray(jnp.nan, jnp.float32)
    return {
        "train_loss_min": jnp.where(ran, acc["loss_min"], nan),
        "train_loss_max": jnp.where(ran, acc["loss_max"], nan),
        "grad_norm_mean": jnp.where(
            ran, acc["gn_sum"] / jnp.maximum(n_steps, 1.0), nan
        ),
        "grad_norm_max": jnp.where(ran, acc["gn_max"], nan),
    }


def make_local_train(
    logic: ClientLogic,
    tx: optax.GradientTransformation,
    metric_manager: MetricManager,
    loss_keys: tuple[str, ...] = ("backward",),
    collect_telemetry: bool = False,
    precision: Any = None,
):
    """Compiled local-training phase: scan the train step over stacked batches.

    Returns train(state, ctx, batches) -> (state, loss_dict, metric_dict,
    n_steps). ``batches`` is a Batch pytree with a leading [steps] axis.
    With ``collect_telemetry`` a fifth output is appended: the engine's
    telemetry dict (loss min/max, grad-norm mean/max over executed steps) —
    extra scan outputs only; the training math is byte-for-byte the same.
    ``precision`` threads the mixed-precision policy into every step (see
    :func:`make_train_step`); telemetry stats are computed from the f32
    boundary values (unscaled grads, f32 losses) either way.
    """
    step_fn = make_train_step(logic, tx, collect_telemetry=collect_telemetry,
                              precision=precision)
    meter_proto = LossMeter.create(loss_keys)

    def _train(state: TrainState, ctx: Any, batches: Batch):
        def body(carry, batch):
            st, meter, mstate, acc = carry
            st, out = step_fn(st, ctx, batch)
            meter = meter.update(out.losses, weight=out.step_mask)
            mstate = metric_manager.update(
                mstate, out.preds, out.targets, out.example_mask
            )
            if collect_telemetry:
                acc = telemetry_acc_update(acc, out)
            return (st, meter, mstate, acc), out.losses

        acc0 = telemetry_acc_init() if collect_telemetry else None
        (state, meter, mstate, acc), _ = jax.lax.scan(
            body, (state, meter_proto, metric_manager.init(), acc0), batches
        )
        n_steps = jnp.sum(batches.step_mask)
        state = logic.finalize_round(state, ctx, n_steps)
        outs = (state, meter.compute(), metric_manager.compute(mstate), n_steps)
        if collect_telemetry:
            return (*outs, telemetry_acc_finalize(acc, n_steps))
        return outs

    def train(state: TrainState, ctx: Any, batches: Batch):
        with stage_attr.stage("local_train"):
            return _train(state, ctx, batches)

    return train


def make_local_eval(
    logic: ClientLogic,
    metric_manager: MetricManager,
    loss_keys: tuple[str, ...] = ("checkpoint",),
):
    """Compiled evaluation phase (validate, basic_client.py:867)."""
    meter_proto = LossMeter.create(loss_keys)

    def evaluate(state: TrainState, ctx: Any, batches: Batch):
        def body(carry, batch):
            meter, mstate, rng = carry
            rng, step_rng = jax.random.split(rng)
            (preds, features), _ = logic.predict(
                state.params, state.model_state, batch, step_rng, train=False,
                extra=state.extra, ctx=ctx,
            )
            loss, additional = logic.eval_loss(
                preds, features, batch, state.params, state, ctx
            )
            meter = meter.update(
                {"checkpoint": loss, **{k: additional[k] for k in meter.sums if k != "checkpoint"}},
                weight=batch.step_mask,
            )
            mstate = metric_manager.update(
                mstate, preds["prediction"], batch.y, batch.example_mask * batch.step_mask
            )
            return (meter, mstate, rng), loss

        (meter, mstate, _), _ = jax.lax.scan(
            body, (meter_proto, metric_manager.init(), state.rng), batches
        )
        return meter.compute(), metric_manager.compute(mstate)

    return evaluate


@dataclasses.dataclass(frozen=True)
class EarlyStoppingConfig:
    """Reference EarlyStopper (utils/early_stopper.py:14): snapshot the best
    state every ``interval_steps`` local steps; stop when validation hasn't
    improved for ``patience`` consecutive checks; restore the best snapshot."""

    interval_steps: int
    patience: int


def make_local_train_with_early_stopping(
    logic: ClientLogic,
    tx: optax.GradientTransformation,
    metric_manager: MetricManager,
    config: EarlyStoppingConfig,
    loss_keys: tuple[str, ...] = ("backward",),
    collect_telemetry: bool = False,
    precision: Any = None,
):
    """Early-stopped local training as ONE compiled program.

    The step stream is chunked into [n_chunks, interval_steps]; after each
    chunk the client validates, tracks the best params snapshot in the scan
    carry, and raises a ``stopped`` flag once patience runs out — subsequent
    chunks have their step_mask zeroed, making them no-ops (the TPU-native
    replacement for breaking out of the reference's Python batch loop,
    basic_client.py:676,755).

    Returns train(state, ctx, batches, val_batches) with the same outputs as
    ``make_local_train`` (including the telemetry dict when
    ``collect_telemetry``; stats cover executed steps only — batches after
    the stop flag have their step_mask zeroed and never touch the
    accumulator). ``precision`` applies to the TRAIN steps only: the
    in-scan validation (and the best-snapshot selection it drives) scores
    the f32 master weights, matching ``fit()``'s eval rounds.
    """
    step_fn = make_train_step(logic, tx, collect_telemetry=collect_telemetry,
                              precision=precision)
    evaluate = make_local_eval(logic, metric_manager)
    meter_proto = LossMeter.create(loss_keys)
    interval = config.interval_steps

    def train(state: TrainState, ctx: Any, batches: Batch, val_batches: Batch):
        total = batches.step_mask.shape[0]
        n_chunks = -(-total // interval)
        pad = n_chunks * interval - total
        if pad:
            batches = jax.tree_util.tree_map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]
                ),
                batches,
            )
        chunked = jax.tree_util.tree_map(
            lambda x: x.reshape((n_chunks, interval) + x.shape[1:]), batches
        )

        def chunk_body(carry, chunk: Batch):
            (st, meter, mstate, acc, best_state, best_score, bad, stopped,
             executed) = carry
            chunk = chunk.replace(step_mask=chunk.step_mask * (1.0 - stopped))

            def body(c, b):
                st2, meter2, ms2, acc2 = c
                st2, out = step_fn(st2, ctx, b)
                meter2 = meter2.update(out.losses, weight=out.step_mask)
                ms2 = metric_manager.update(
                    ms2, out.preds, out.targets, out.example_mask
                )
                if collect_telemetry:
                    acc2 = telemetry_acc_update(acc2, out)
                return (st2, meter2, ms2, acc2), None

            (st, meter, mstate, acc), _ = jax.lax.scan(
                body, (st, meter, mstate, acc), chunk
            )
            executed = executed + jnp.sum(chunk.step_mask)

            val_losses, _ = evaluate(st, ctx, val_batches)
            score = val_losses["checkpoint"]
            live = stopped < 0.5
            improved = (score < best_score) & live
            best_state = _mask_tree(st, best_state, improved)
            best_score = jnp.where(improved, score, best_score)
            bad = jnp.where(live, jnp.where(improved, 0, bad + 1), bad)
            stopped = jnp.maximum(
                stopped, (bad >= config.patience).astype(jnp.float32)
            )
            return (st, meter, mstate, acc, best_state, best_score, bad,
                    stopped, executed), score

        init = (
            state,
            meter_proto,
            metric_manager.init(),
            telemetry_acc_init() if collect_telemetry else None,
            state,
            jnp.asarray(jnp.inf, jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (final, meter, mstate, acc, best_state, _, _, _, executed), _ = (
            jax.lax.scan(chunk_body, init, chunked)
        )
        # restore the FULL best snapshot — params, optimizer, model_state and
        # algorithm extra move together (the reference snapshots model AND
        # optimizer state, early_stopper.py:46,90); keep the advanced RNG so
        # randomness is never replayed. finalize_round then runs on the
        # restored state, matching update_after_train-after-restore ordering.
        state = best_state.replace(rng=final.rng)
        state = logic.finalize_round(state, ctx, executed)
        outs = (state, meter.compute(), metric_manager.compute(mstate), executed)
        if collect_telemetry:
            return (*outs, telemetry_acc_finalize(acc, executed))
        return outs

    return train


# ---------------------------------------------------------------------------
# Host-side batching: DataLoader equivalent producing static-shaped stacks
# ---------------------------------------------------------------------------
#
# Index construction is pure numpy (zero device dispatches); the only device
# work per round is ONE gather per array. At 64 clients the previous per-step
# jnp indexing was thousands of tiny dispatches per round — the reference's
# eager-DataLoader dispatch pattern this build exists to eliminate.


def _entropy_from_key(rng: PRNGKey) -> list[int]:
    """Stable integer entropy from a JAX PRNG key (legacy uint32 or typed)."""
    try:
        data = np.asarray(jax.random.key_data(rng))
    except (TypeError, ValueError):
        data = np.asarray(rng)
    return [int(v) for v in data.ravel()]


def epoch_index_plan(
    entropy: list[int],
    n: int,
    batch_size: int,
    n_steps: int | None = None,
    shuffle: bool = True,
    drop_last: bool = False,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized batch-index plan: (idx [S,B] i32, example_mask [S,B] f32,
    step_mask [S] f32), all numpy.

    Semantics match the reference loader: one epoch (or exactly n_steps,
    wrapping with a fresh shuffle at each epoch boundary — train_by_steps
    cycles its loader, basic_client.py:699); ragged final batch rows get
    example_mask 0.
    """
    steps_per_epoch = max(1, n // batch_size if drop_last else -(-n // batch_size))
    total = n_steps if n_steps is not None else steps_per_epoch
    n_epochs = -(-total // steps_per_epoch)

    rng = np.random.default_rng(np.random.SeedSequence(entropy))
    if shuffle:
        orders = rng.permuted(
            np.tile(np.arange(n, dtype=np.int32), (n_epochs, 1)), axis=1
        )
    else:
        orders = np.tile(np.arange(n, dtype=np.int32), (n_epochs, 1))

    padded_len = steps_per_epoch * batch_size
    if padded_len <= n:
        epoch_idx = orders[:, :padded_len]
        epoch_mask = np.ones((padded_len,), np.float32)
    else:
        pad = padded_len - n
        epoch_idx = np.concatenate(
            [orders, np.zeros((n_epochs, pad), np.int32)], axis=1
        )
        epoch_mask = np.concatenate(
            [np.ones((n,), np.float32), np.zeros((pad,), np.float32)]
        )

    idx = epoch_idx.reshape(n_epochs * steps_per_epoch, batch_size)[:total]
    example_mask = np.tile(
        epoch_mask.reshape(steps_per_epoch, batch_size), (n_epochs, 1)
    )[:total]
    # A step with zero valid examples (e.g. an empty client dataset) is a full
    # no-op: the engine gates optimizer/meter updates on step_mask.
    step_mask = (example_mask.sum(axis=1) > 0).astype(np.float32)
    return idx, example_mask, step_mask


def epoch_batches(
    rng: PRNGKey,
    x: jax.Array,
    y: jax.Array,
    batch_size: int,
    n_steps: int | None = None,
    shuffle: bool = True,
    drop_last: bool = False,
) -> Batch:
    """Build a [steps, B, ...] Batch stack for one epoch (or exactly n_steps).

    If n_steps exceeds one epoch, batches wrap around (reference
    train_by_steps cycles its loader); if it's shorter, the epoch is truncated.
    Padding rows get example_mask 0; padding steps get step_mask 0.
    ``x``/``y`` may be pytrees of arrays sharing axis 0 (dict inputs).
    """
    # x AND y leaves must agree on axis-0 size: without this, the gather
    # below would CLAMP out-of-range indices on short leaves — silently
    # repeating rows instead of erroring (direct callers like the
    # fedprox_cluster silo handler bypass FederatedSimulation's nx==ny check)
    ns = {
        leaf.shape[0]
        for tree in (x, y)
        for leaf in jax.tree_util.tree_leaves(tree)
    }
    if len(ns) > 1:
        raise ValueError(
            f"epoch_batches: x/y leaves disagree on example count: {sorted(ns)}"
        )
    idx, example_mask, step_mask = epoch_index_plan(
        _entropy_from_key(rng), data_rows(x), batch_size, n_steps, shuffle,
        drop_last,
    )
    idx_arr = jnp.asarray(idx)
    take = lambda a: a[idx_arr]  # noqa: E731
    return Batch(
        x=jax.tree_util.tree_map(take, x),
        y=jax.tree_util.tree_map(take, y),
        example_mask=jnp.asarray(example_mask),
        step_mask=jnp.asarray(step_mask),
    )


def multi_client_index_plans(
    entropies: list[list[int]],
    ns: list[int],
    batch_size: int,
    n_steps: int | None = None,
    local_epochs: int | None = None,
    shuffle: bool = True,
    pad_steps: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Cohort-wide batch plan: (idx [C,S,B], example_mask [C,S,B],
    step_mask [C,S]) numpy arrays, padded to the cohort's max step count.

    Pure host-side index math — the per-client DataLoader loop collapsed into
    one plan that feeds a single device gather (``gather_batches``).

    ``pad_steps`` pins the step axis to a FIXED length instead of the
    cohort's max (extra steps carry step_mask 0, full no-ops). Cohort-slot
    rounds (``server/registry.py``) pad every round's plan to the
    REGISTRY-wide step budget so the compiled slot program's shape never
    depends on which clients were sampled. Raises if any client's plan
    exceeds it.
    """
    plans = []
    for ent, n in zip(entropies, ns):
        if local_epochs is not None:
            parts = [
                epoch_index_plan([*ent, e], n, batch_size, None, shuffle)
                for e in range(local_epochs)
            ]
            idx = np.concatenate([p[0] for p in parts], axis=0)
            em = np.concatenate([p[1] for p in parts], axis=0)
            sm = np.concatenate([p[2] for p in parts], axis=0)
        else:
            idx, em, sm = epoch_index_plan(ent, n, batch_size, n_steps, shuffle)
        plans.append((idx, em, sm))
    n_clients = len(plans)
    max_steps = max(p[0].shape[0] for p in plans)
    if pad_steps is not None:
        if max_steps > pad_steps:
            raise ValueError(
                f"pad_steps={pad_steps} is smaller than the largest "
                f"client plan ({max_steps} steps); the fixed step budget "
                "must cover every client in the registry"
            )
        max_steps = pad_steps
    idx_all = np.zeros((n_clients, max_steps, batch_size), np.int32)
    em_all = np.zeros((n_clients, max_steps, batch_size), np.float32)
    sm_all = np.zeros((n_clients, max_steps), np.float32)
    for c, (idx, em, sm) in enumerate(plans):
        s = idx.shape[0]
        idx_all[c, :s] = idx
        em_all[c, :s] = em
        sm_all[c, :s] = sm
    return idx_all, em_all, sm_all


def data_rows(tree) -> int:
    """Example count of a data pytree (axis-0 length of its first leaf) —
    the one place "how many rows" is defined for array and dict data alike."""
    return int(jax.tree_util.tree_leaves(tree)[0].shape[0])


def pad_and_stack_data(arrays: list, name: str = "data"):
    """Zero-pad along axis 0 to the max length and stack -> [C, max_n, ...],
    leafwise over a data PYTREE (a plain array, or a dict of arrays — the
    reference's DictionaryDataset role, utils/dataset.py:DictionaryDataset:
    multi-input models take {"input_ids": ..., "attention_mask": ...}-style
    batches; here any pytree x flows through the same stacked-gather path).

    Setup-time only; padding rows are never selected by a valid index plan.
    Assembly happens on HOST (numpy) with a single device transfer at the
    end. Pass numpy arrays in ClientDataset to avoid any device round-trip.
    """
    treedef = jax.tree_util.tree_structure(arrays[0])
    for i, a in enumerate(arrays):
        if jax.tree_util.tree_structure(a) != treedef:
            raise ValueError(
                f"client {i}'s {name} pytree structure "
                f"{jax.tree_util.tree_structure(a)} differs from client 0's "
                f"{treedef}; every client must provide the same input keys."
            )
    flat = [jax.tree_util.tree_flatten_with_path(a)[0] for a in arrays]
    # within each client, every leaf must carry the same number of examples
    for i, leaves in enumerate(flat):
        ns = {path_str(path): leaf.shape[0] for path, leaf in leaves}
        if len(set(ns.values())) > 1:
            raise ValueError(
                f"client {i}'s {name} leaves disagree on example count: {ns}"
            )
    # data-staging observability: this is the DataLoader-boundary cost (host
    # assembly + one device transfer), paid at setup / per-round refresh —
    # the span is a shared no-op while the process tracer is disabled
    with get_tracer().span(
        "pad_and_stack", cat="data", dataset=name, clients=len(arrays)
    ) as sp:
        out_leaves = [
            _pad_and_stack_leaf(
                [leaves[j][1] for leaves in flat],
                name + path_str(flat[0][j][0]),
            )
            for j in range(len(flat[0]))
        ]
        staged = tree_nbytes(out_leaves)
        sp.set(staged_bytes=staged)
    get_registry().counter(
        "engine_staged_bytes_total",
        help="bytes staged into client-stacked device arrays "
             "(setup + per-round data refresh)",
    ).inc(staged)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def path_str(path) -> str:
    """Readable suffix for a tree path in error messages ("" for the root,
    i.e. plain-array data). Delegates to jax's canonical renderer."""
    return jax.tree_util.keystr(path) if path else ""


def _pad_and_stack_leaf(arrays: list[jax.Array], name: str) -> jax.Array:
    host = [np.asarray(a) for a in arrays]
    # The cohort shares one compiled program: every client's example shape
    # and dtype must agree. Name the offending client and array instead of
    # letting numpy's broadcast error (or a silent cast — float labels
    # truncated into an int slot) surface from deep inside setup.
    base = host[0].shape[1:]
    for i, a in enumerate(host):
        if a.shape[1:] != base:
            raise ValueError(
                f"client {i}'s {name} has per-example shape {a.shape[1:]} "
                f"but client 0 has {base}; all clients in a cohort must "
                "share one example shape (align features before building "
                "the simulation — e.g. the tabular feature-alignment "
                "protocol)."
            )
        if a.dtype != host[0].dtype:
            raise ValueError(
                f"client {i}'s {name} has dtype {a.dtype} but client 0 has "
                f"{host[0].dtype}; stacking would silently cast — convert "
                "the clients' data to one dtype first."
            )
    max_n = max(a.shape[0] for a in host)
    stack = np.zeros((len(host), max_n, *base), host[0].dtype)
    for i, a in enumerate(host):
        stack[i, : a.shape[0]] = a
    return jnp.asarray(stack)


def gather_batches(
    x_stack,
    y_stack,
    idx: np.ndarray,
    example_mask: np.ndarray,
    step_mask: np.ndarray,
) -> Batch:
    """One device-side gather from pre-stacked data -> [C,S,B,...] Batch.
    ``x_stack``/``y_stack`` may be pytrees (dict inputs); the same index
    plan gathers every leaf."""
    idx_arr = jnp.asarray(idx)
    c = jnp.arange(idx_arr.shape[0])[:, None, None]
    gather = lambda s: s[c, idx_arr]  # noqa: E731
    return Batch(
        x=jax.tree_util.tree_map(gather, x_stack),
        y=jax.tree_util.tree_map(gather, y_stack),
        example_mask=jnp.asarray(example_mask),
        step_mask=jnp.asarray(step_mask),
    )


def pad_batch_stacks(stacks: list[Batch]) -> Batch:
    """Pad per-client Batch stacks to a common [steps] length and stack along a
    new leading clients axis -> [clients, steps, B, ...]."""
    max_steps = max(b.step_mask.shape[0] for b in stacks)

    def pad_leaf(a, pad):
        return jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])

    def pad_one(b: Batch) -> Batch:
        pad = max_steps - b.step_mask.shape[0]
        if pad == 0:
            return b
        # x/y may be pytrees (dict inputs) — pad every leaf
        return Batch(
            x=jax.tree_util.tree_map(lambda a: pad_leaf(a, pad), b.x),
            y=jax.tree_util.tree_map(lambda a: pad_leaf(a, pad), b.y),
            example_mask=jnp.concatenate(
                [b.example_mask, jnp.zeros((pad, *b.example_mask.shape[1:]), jnp.float32)]
            ),
            step_mask=jnp.concatenate([b.step_mask, jnp.zeros((pad,), jnp.float32)]),
        )

    padded = [pad_one(b) for b in stacks]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *padded)
