"""Round-program builder — mesh + sharding as a property of the compiled program.

Every compiled round program (``_fit_round[_t]``, ``_eval_round[_t]``,
``fit_chunk``, ``fit_chunk_eval`` and the servers' warm-start jits) is
constructed HERE, so placement policy lives in exactly one place:

- ``mesh=None`` (the default): :meth:`RoundProgramBuilder.jit` is a plain
  ``jax.jit(fn, donate_argnums=...)`` — byte-for-byte the pre-mesh build,
  keeping the single-chip trajectories bit-identical.
- With a :class:`MeshConfig`: the ``[C, ...]`` client-stacked axes get
  ``NamedSharding(P("clients"))`` via ``in_shardings``/``out_shardings``,
  the server state replicates (or ZeRO-1 shards its optimizer vectors over
  the replicas), and XLA inserts the broadcast/reduce collectives — one FL
  client cohort spread over data-parallel devices (ROADMAP item 1; FedJAX's
  massive-cohort regime, arXiv:2108.02117).

Axis semantics follow ``parallel/mesh.py``: "clients" is federated data
parallelism, "model" is tensor parallelism within each client slice
(``parallel/tp.py`` Megatron column/row rules, applied per-leaf when
``tp_rules=True``). Cross-replica sharding of the server optimizer update
(``zero1=True``) wires ``parallel/zero.py`` into a FedOpt-family strategy:
each replica owns 1/N of the server optimizer state and the weight update
gathers once per round (Xu et al., "Automatic Cross-Replica Sharding of
Weight Update").

Donation routes through the same CPU gating as
``simulation._donate_argnums`` (the persistent-cache aliased-executable
bug — wrong numerics when a donated executable is reloaded from a warm
``.jax_test_cache`` on XLA:CPU), so a sharded program is never MORE
donation-prone than the single-chip one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fl4health_tpu.parallel import mesh as meshlib
from fl4health_tpu.parallel import tp as tplib

CLIENTS_AXIS = "clients"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh request for :class:`FederatedSimulation`.

    ``clients``: devices along the "clients" axis (None = every available
    device after the model axis is carved out). ``model`` > 1 builds the
    hybrid ``(clients, model)`` mesh for tensor-parallel transformer
    configs. ``zero1`` shards the SERVER optimizer state (FedOpt-family
    strategies) over the clients replicas — ZeRO stage 1 applied to the
    server update. ``tp_rules`` applies ``parallel/tp.py``'s Megatron
    column/row rules per param leaf (transformer models; everything
    unmatched replicates over "model"). ``validate_zero1`` runs the
    construction-time sharded-vs-unsharded parity probe of
    ``parallel/zero.py`` against THIS mesh — the one ``fit()`` actually
    dispatches on — so validation reflects the deployed sharding.
    """

    clients: int | None = None
    model: int = 1
    zero1: bool = False
    tp_rules: bool = False
    validate_zero1: bool = True

    def __post_init__(self):
        if self.model < 1:
            raise ValueError(f"MeshConfig.model must be >= 1, got {self.model}")
        if self.clients is not None and self.clients < 1:
            raise ValueError(
                f"MeshConfig.clients must be >= 1, got {self.clients}"
            )
        if self.tp_rules and self.model < 2:
            raise ValueError(
                "MeshConfig.tp_rules needs a model axis (model >= 2): the "
                "TP rules would silently no-op on a 1-wide axis"
            )

    def build(self, devices: Sequence[Any] | None = None) -> Mesh:
        devices = list(devices) if devices is not None else jax.devices()
        n_clients_axis = self.clients or max(len(devices) // self.model, 1)
        needed = n_clients_axis * self.model
        if needed > len(devices):
            raise ValueError(
                f"MeshConfig needs {n_clients_axis}x{self.model} = {needed} "
                f"devices but only {len(devices)} are visible"
            )
        if self.model > 1:
            return meshlib.hybrid_mesh(n_clients_axis, self.model,
                                       devices=devices)
        return meshlib.client_mesh(n_clients_axis, devices=devices)


class RoundProgramBuilder:
    """Single construction point for compiled round programs.

    With ``config=None`` every helper returns ``None`` and :meth:`jit`
    degenerates to plain ``jax.jit`` + donation gating — the pre-mesh
    program, bit-identical. With a mesh, the helpers hand back the
    ``NamedSharding`` trees the round programs are jitted with.
    """

    def __init__(self, config: MeshConfig | None = None, *,
                 n_clients: int | None = None,
                 devices: Sequence[Any] | None = None):
        self.config = config
        self.mesh: Mesh | None = None
        if config is not None:
            self.mesh = config.build(devices)
            n_axis = self.client_axis_size
            if n_clients is not None and n_clients % n_axis != 0:
                raise ValueError(
                    f"n_clients={n_clients} must be divisible by the "
                    f"clients mesh axis ({n_axis} devices): XLA shards the "
                    "leading [C] axis evenly — pad the cohort or shrink the "
                    "axis (MeshConfig(clients=...))"
                )

    # -- facts -----------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    @property
    def client_axis_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[CLIENTS_AXIS])

    @property
    def spmd_axis_name(self) -> str | None:
        """``spmd_axis_name`` for the ``vmap`` over clients: with a mesh, a
        ``shard_map`` inside the vmapped client step (the Pallas kernels'
        wrapper, see :meth:`jit`) learns that the batched axis is the
        "clients" mesh axis."""
        return CLIENTS_AXIS if self.mesh is not None else None

    def descriptor(self) -> dict | None:
        """JSON-able mesh + sharding-policy descriptor (manifest /
        ``fl_program_*`` events / bench ``mesh`` block)."""
        if self.mesh is None:
            return None
        desc = meshlib.mesh_descriptor(self.mesh)
        desc["zero1"] = bool(self.config.zero1)
        desc["tp_rules"] = bool(self.config.tp_rules)
        return desc

    # -- donation gating -------------------------------------------------
    @staticmethod
    def donate(*argnums: int) -> tuple[int, ...]:
        """Buffer donation, gated OFF the CPU backend — the SAME rule as
        ``simulation._donate_argnums`` (persistent-cache mis-restore of
        aliased executables on XLA:CPU; see that docstring and the repo
        memory note). Sharded programs go through this too: in_shardings/
        out_shardings do not change the aliasing hazard."""
        return argnums if jax.default_backend() != "cpu" else ()

    # -- sharding trees --------------------------------------------------
    def named(self, spec: P) -> NamedSharding | None:
        return NamedSharding(self.mesh, spec) if self.mesh is not None else None

    def client_sharding(self) -> NamedSharding | None:
        """Leading-[C]-axis sharding for client-stacked trees (states,
        batches, masks, per-client counts)."""
        return self.named(P(CLIENTS_AXIS))

    def stacked_client_sharding(self) -> NamedSharding | None:
        """[rounds, C, ...] chunk inputs: clients on axis 1.

        The cohort chunked route's window trees ([W, ...] registry rows,
        W = min(N, R*K)) deliberately do NOT get a sharding helper: W is
        not a multiple of the device count in general, and the in-graph
        searchsorted gather/scatter against the window would resolve to
        cross-device collectives per scan step. That is why mesh + cohort
        demotes to the pipelined path (simulation._chunk_ineligibility)
        instead of running a sharded window exchange."""
        return self.named(P(None, CLIENTS_AXIS))

    def replicated(self) -> NamedSharding | None:
        return self.named(P())

    def client_state_shardings(self, template: Any) -> Any:
        """Sharding (tree) for the client-stacked ``TrainState``.

        Default: one ``P("clients")`` prefix — every leaf carries a leading
        [C] axis. With ``tp_rules`` the params/opt_state subtrees get
        per-leaf hybrid specs (``P("clients", <tp dims>)``) keyed on the
        transformer module names (``parallel/tp.py``)."""
        if self.mesh is None:
            return None
        cs = self.client_sharding()
        if not self.config.tp_rules:
            return cs
        params_t = template.params

        def place(subtree):
            # optimizer momenta etc. inherit their param's rule by
            # dotted-path SUFFIX — THE tp.py implementation, so a rule
            # change there reaches the mesh-built round programs
            specs = tplib.spec_like_params(
                subtree, params_t, axis=MODEL_AXIS, client_axis=CLIENTS_AXIS,
                default=P(CLIENTS_AXIS),
            )
            return jax.tree_util.tree_map(
                lambda _leaf, spec: self.named(spec), subtree, specs
            )

        return template.replace(
            params=place(params_t),
            opt_state=place(template.opt_state),
            model_state=cs,
            rng=cs,
            step=cs,
            extra=cs if jax.tree_util.tree_leaves(template.extra) else None,
            # fp16 scaler state: [C]-leading scalars, clients-axis like the
            # other per-client bookkeeping (None when precision is off /
            # not scaling, matching the template's empty node)
            loss_scale=(
                cs if jax.tree_util.tree_leaves(template.loss_scale)
                else None
            ),
        )

    def server_state_shardings(self, strategy: Any, template: Any) -> Any:
        """Sharding (tree) for the server state: fully replicated unless the
        strategy declares per-leaf specs via ``state_sharding_spec`` (the
        ZeRO-1 server optimizer, wrapper strategies' per-client [C]
        bookkeeping)."""
        if self.mesh is None:
            return None
        spec_tree = None
        hook = getattr(strategy, "state_sharding_spec", None)
        if hook is not None:
            spec_tree = hook(template, CLIENTS_AXIS)
        if spec_tree is None:
            return self.replicated()
        return jax.tree_util.tree_map(
            lambda s: self.named(s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def put(self, tree: Any, sharding: Any) -> Any:
        """``device_put`` a pytree onto a sharding (tree or prefix); no-op
        without a mesh. The prefetcher uses this for per-round sharded data
        staging."""
        if self.mesh is None or sharding is None:
            return tree
        return jax.device_put(tree, sharding)

    # -- the one jit -----------------------------------------------------
    def jit(self, fn, *, donate: tuple[int, ...] = (),
            in_shardings: Any = None, out_shardings: Any = None):
        """``jax.jit`` with the builder's placement policy applied.

        Without a mesh this is EXACTLY ``jax.jit(fn, donate_argnums=
        donate-after-CPU-gating)`` — no sharding arguments are constructed
        at all, so the single-chip programs (and their persistent-cache
        keys) are unchanged. With a mesh, ``in_shardings``/``out_shardings``
        (trees of ``NamedSharding`` / ``None`` = unconstrained) pin the
        client axis split and keep the state outputs sharded — a round
        program can never silently gather the cohort onto one chip. The
        program is TRACED with the mesh in context
        (``jax.sharding.get_abstract_mesh()``): XLA cannot auto-partition a
        Mosaic custom call, so a Pallas kernel inside the round
        (``kernels/flash_attention.py``) wraps itself in a ``shard_map``
        over the mesh it finds there."""
        donate_argnums = self.donate(*donate)
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        abstract_mesh = self.mesh.abstract_mesh

        @functools.wraps(fn)
        def traced_under_mesh(*args, **kwargs):
            with jax.sharding.use_abstract_mesh(abstract_mesh):
                return fn(*args, **kwargs)

        kwargs: dict[str, Any] = {"donate_argnums": donate_argnums}
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        return jax.jit(traced_under_mesh, **kwargs)
