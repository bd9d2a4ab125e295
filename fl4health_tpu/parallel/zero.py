"""ZeRO-style sharded optimizer state — shard_map over a mesh axis.

The reference delegates optimizer-state sharding to DeepSpeed ZeRO configs in
the FedLLM example (/root/reference/examples/fedllm_example README's
zero2/zero3 JSONs; SURVEY §2.1 item d names the TPU equivalent a first-class
component). TPU-native design: ZeRO-1 as a wrapper around ANY optax
transformation — the flat parameter vector is partitioned over a mesh axis;
each device holds and updates only its 1/N slice of optimizer state (momenta
etc.); the updates come back as one logically-full (sharded) vector that
optax.apply_updates consumes, XLA inserting the all-gather where the
consumer needs it. This is exactly the memory split of ZeRO stage 1: O(P/N)
optimizer state per device at the cost of one gather per step over ICI.

SCOPE: the wrapped transform must be ELEMENTWISE over the flat parameter
vector (sgd, momentum, adam/adamw, rmsprop, ...). Transforms that reduce
across ALL parameters — clip_by_global_norm, lamb/lars trust ratios,
adafactor row/col stats — would compute shard-local statistics inside
shard_map and silently diverge from the unsharded optimizer. Apply such
transforms OUTSIDE the wrapper (their state is O(1), there is nothing to
shard) and wrap only the elementwise tail. This contract is CHECKED at
construction: the factory runs one-step sharded-vs-unsharded parity probes
at two gradient magnitudes on the params template and raises on divergence
(``validate=False`` skips). The probe is a strong guard, not a proof — a
coupling active only at untested scales can slip through; the elementwise
rule remains the contract.

ZeRO-2 (``Zero2ShardedOptimizer``): additionally shards the gradient
REDUCTION. ``update`` takes per-device UNREDUCED gradient trees (leading
[n_shards] axis); inside shard_map the sum happens as a ``psum_scatter`` so
each device only ever materializes its 1/N slice of the summed gradient —
the ZeRO stage-2 memory split (grads O(P/N) + optimizer state O(P/N)) — and
the updated slices return through one tiled ``all_gather``. Role of the
reference's DeepSpeed zero2 config in the fedllm example.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.core.types import Params


@dataclasses.dataclass(frozen=True)
class ZeroShardedOptimizer:
    """optax-compatible (init/update) with state sharded over ``axis_name``.

    Built from a ``params_template`` so the flat<->tree transforms are static
    (shard_map needs static specs). Use ``state_sharding(state)`` to inspect
    placement in tests.
    """

    tx: optax.GradientTransformation
    mesh: Mesh
    axis_name: str = "model"
    params_template: Params | None = None

    def _flat_size(self) -> tuple[int, int]:
        flat, _ = ptu.ravel(self.params_template)
        n_shards = self.mesh.shape[self.axis_name]
        padded = -(-flat.shape[0] // n_shards) * n_shards
        return flat.shape[0], padded

    # -- optax surface ------------------------------------------------------
    def init(self, params: Params) -> Any:
        size, padded = self._flat_size()
        flat, _ = ptu.ravel(params)
        flat = jnp.concatenate([flat, jnp.zeros((padded - size,), flat.dtype)])
        state = self.tx.init(flat)
        # Shard every vector-shaped state leaf; scalars (counts) replicate.
        shard = NamedSharding(self.mesh, P(self.axis_name))
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(
                leaf, shard if getattr(leaf, "ndim", 0) >= 1 else rep
            ),
            state,
        )

    def update(self, grads: Params, opt_state: Any, params: Params | None = None):
        size, padded = self._flat_size()
        pad = padded - size
        flat_g, unravel = ptu.ravel(grads)
        flat_g = jnp.concatenate([flat_g, jnp.zeros((pad,), flat_g.dtype)])
        if params is not None:
            flat_p, _ = ptu.ravel(params)
            flat_p = jnp.concatenate([flat_p, jnp.zeros((pad,), flat_p.dtype)])
        else:
            flat_p = None

        vec_spec = P(self.axis_name)
        state_specs = jax.tree_util.tree_map(
            lambda leaf: vec_spec if getattr(leaf, "ndim", 0) >= 1 else P(),
            opt_state,
        )

        def shard_update(g, state, p):
            return self.tx.update(g, state, p)

        updates_flat, new_state = jax.shard_map(
            shard_update,
            mesh=self.mesh,
            in_specs=(vec_spec, state_specs, vec_spec if flat_p is not None else None),
            out_specs=(vec_spec, state_specs),
            check_vma=False,
        )(flat_g, opt_state, flat_p)
        return unravel(updates_flat[:size]), new_state

    # -- introspection ------------------------------------------------------
    def state_bytes_per_device(self, opt_state: Any) -> int:
        """Bytes of optimizer state resident per device (the ZeRO win)."""
        n = self.mesh.shape[self.axis_name]
        total = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(opt_state)
            if getattr(leaf, "ndim", 0) >= 1
        )
        return total // n


@dataclasses.dataclass(frozen=True)
class Zero2ShardedOptimizer:
    """ZeRO-2: sharded gradient reduction + sharded optimizer state.

    ``update(local_grads, opt_state, params)`` takes a grads pytree whose
    leaves carry a leading [n_shards] axis — one UNREDUCED gradient per mesh
    slot (e.g. per-microbatch or per-client grads destined for averaging).
    The reduction runs as ``psum_scatter`` inside shard_map, so the full
    summed gradient vector is never materialized on any device.

    ``reduce="mean"`` divides by n_shards (the data-parallel convention);
    ``"sum"`` leaves the psum as-is.
    """

    tx: optax.GradientTransformation
    mesh: Mesh
    axis_name: str = "model"
    params_template: Params | None = None
    reduce: str = "mean"

    # Engine handshake (clients/engine.py make_train_step): optimizers that
    # set this receive a [n_shards]-leading stack of UNREDUCED gradient
    # trees instead of one reduced tree — the engine computes per-microbatch
    # grads and lets the psum_scatter below do the reduction.
    expects_unreduced_grads = True

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis_name]

    def _flat_size(self) -> tuple[int, int]:
        flat, _ = ptu.ravel(self.params_template)
        n_shards = self.mesh.shape[self.axis_name]
        padded = -(-flat.shape[0] // n_shards) * n_shards
        return flat.shape[0], padded

    def init(self, params: Params) -> Any:
        # Same state layout as ZeRO-1: each device owns 1/N of every vector
        # leaf (ZeRO-2 differs in how gradients ARRIVE, not in what is kept).
        return ZeroShardedOptimizer(
            self.tx, self.mesh, self.axis_name, self.params_template
        ).init(params)

    def update(self, local_grads: Params, opt_state: Any,
               params: Params | None = None):
        size, padded = self._flat_size()
        pad = padded - size
        n_shards = self.mesh.shape[self.axis_name]

        # [n_shards, padded] stack of flat local grads.
        def flatten_one(i):
            g_i = jax.tree_util.tree_map(lambda x: x[i], local_grads)
            flat, _ = ptu.ravel(g_i)
            return jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])

        flat_stack = jnp.stack([flatten_one(i) for i in range(n_shards)])
        _, unravel = ptu.ravel(self.params_template)
        if params is not None:
            flat_p, _ = ptu.ravel(params)
            flat_p = jnp.concatenate([flat_p, jnp.zeros((pad,), flat_p.dtype)])
        else:
            flat_p = None

        vec_spec = P(self.axis_name)
        stack_spec = P(self.axis_name, None)
        state_specs = jax.tree_util.tree_map(
            lambda leaf: vec_spec if getattr(leaf, "ndim", 0) >= 1 else P(),
            opt_state,
        )
        scale = 1.0 / n_shards if self.reduce == "mean" else 1.0

        def shard_update(g_local, state, p):
            # g_local: [1, padded] — this device's unreduced gradient.
            # psum_scatter sums across devices AND hands each device only its
            # 1/N slice of the result: the full summed vector never exists.
            g_shard = jax.lax.psum_scatter(
                g_local[0], self.axis_name, scatter_dimension=0, tiled=True
            ) * scale
            upd_shard, new_state = self.tx.update(g_shard, state, p)
            upd_full = jax.lax.all_gather(
                upd_shard, self.axis_name, tiled=True
            )
            return upd_full, new_state

        updates_flat, new_state = jax.shard_map(
            shard_update,
            mesh=self.mesh,
            in_specs=(stack_spec, state_specs,
                      vec_spec if flat_p is not None else None),
            out_specs=(P(), state_specs),
            check_vma=False,
        )(flat_stack, opt_state, flat_p)
        return unravel(updates_flat[:size]), new_state

    def grad_bytes_per_device(self) -> int:
        """Bytes of summed gradient resident per device during the update —
        the stage-2 claim: 1/N of the full vector."""
        size, padded = self._flat_size()
        flat, _ = ptu.ravel(self.params_template)
        return (padded // self.mesh.shape[self.axis_name]) * flat.dtype.itemsize

    def state_bytes_per_device(self, opt_state: Any) -> int:
        n = self.mesh.shape[self.axis_name]
        total = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(opt_state)
            if getattr(leaf, "ndim", 0) >= 1
        )
        return total // n


def _probe_grads(params_template: Params, scale: float):
    """Deterministic, value-varied probe gradients: catches transforms whose
    update depends on cross-parameter statistics (norms, trust ratios) that
    a shard-local computation would get wrong."""
    flat, unravel = ptu.ravel(params_template)
    g = jnp.sin(jnp.arange(flat.shape[0], dtype=flat.dtype) * 0.37) * scale
    return unravel(g), flat, g


def _validate_elementwise(wrapper, tx, params_template, n_local=None):
    """One-step sharded-vs-unsharded parity probe. Raises ValueError when the
    wrapped transform is not elementwise over the flat vector (e.g.
    clip_by_global_norm, adafactor).

    Probes run at a SMALL and a LARGE gradient magnitude: cross-parameter
    couplings are often conditional (a clip threshold binds only above it, a
    trust ratio saturates below it), and a single-scale probe would certify a
    transform whose coupling simply wasn't active at that scale. Two scales
    are a strong heuristic, not an exhaustive proof — a transform whose
    reduction activates only in some exotic band can still slip through, so
    the SCOPE rule remains the contract."""
    for scale in (1e-2, 1e3):
        gtree, flat_p, flat_g = _probe_grads(params_template, scale)
        ref_state = tx.init(flat_p)
        ref_upd, _ = tx.update(flat_g, ref_state, flat_p)

        sharded_state = wrapper.init(params_template)
        if n_local is None:
            upd_tree, _ = wrapper.update(gtree, sharded_state, params_template)
        else:
            # ZeRO-2 consumes per-device unreduced grads. n identical copies
            # of g reduce to g under "mean"; n copies of g/n reduce to g
            # under "sum" — either way the effective gradient matches the
            # unsharded reference.
            div = 1.0 if wrapper.reduce == "mean" else float(n_local)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.stack([x / div] * n_local), gtree
            )
            upd_tree, _ = wrapper.update(stacked, sharded_state,
                                         params_template)
        got, _ = ptu.ravel(upd_tree)
        # Tolerance scales with the update magnitude: a fixed atol would
        # swallow small-update divergences (e.g. a tightly-clipped gradient,
        # exactly the class of transform the probe exists to catch).
        atol = 1e-5 * float(jnp.max(jnp.abs(ref_upd))) + 1e-30
        if not bool(jnp.allclose(got, ref_upd, rtol=1e-4, atol=atol)):
            err = float(jnp.max(jnp.abs(got - ref_upd)))
            raise ValueError(
                "ZeRO parity probe failed at gradient scale "
                f"{scale:g} (max |Δupdate| = "
                f"{err:.3e}): the wrapped transform is not elementwise over "
                "the flat parameter vector (global-norm clipping, trust "
                "ratios and adafactor-style factored stats reduce ACROSS "
                "parameters and diverge silently when sharded). Apply such "
                "transforms outside the wrapper and wrap only the "
                "elementwise tail, or pass validate=False if you know "
                "better."
            )


def zero_sharded_optimizer(
    tx: optax.GradientTransformation,
    mesh: Mesh,
    params_template: Params,
    axis_name: str = "model",
    validate: bool = True,
) -> ZeroShardedOptimizer:
    opt = ZeroShardedOptimizer(
        tx=tx, mesh=mesh, axis_name=axis_name, params_template=params_template
    )
    if validate:
        _validate_elementwise(opt, tx, params_template)
    return opt


def zero2_sharded_optimizer(
    tx: optax.GradientTransformation,
    mesh: Mesh,
    params_template: Params,
    axis_name: str = "model",
    reduce: str = "mean",
    validate: bool = True,
) -> Zero2ShardedOptimizer:
    if reduce not in ("mean", "sum"):
        raise ValueError(f"reduce must be 'mean' or 'sum', got {reduce!r}")
    opt = Zero2ShardedOptimizer(
        tx=tx, mesh=mesh, axis_name=axis_name,
        params_template=params_template, reduce=reduce,
    )
    if validate:
        _validate_elementwise(
            opt, tx, params_template, n_local=mesh.shape[axis_name]
        )
    return opt
