"""Strategy abstraction — server-side aggregation as pure functions.

Reference surface: flwr Strategy subclasses in /root/reference/fl4health/strategies/
own configure_fit/aggregate_fit/aggregate_evaluate plus wire pack/unpack.

TPU-native design: a Strategy owns a ``ServerState`` pytree and two pure
functions — ``client_payload`` (what every client receives this round;
broadcast is free under SPMD) and ``aggregate`` (stacked client packets ->
new server state), both jit-compiled into the round program. Client sampling
lives in ``fl4health_tpu.server.client_manager`` and produces a mask, so a
partially-sampled cohort never changes program shapes.
"""

from __future__ import annotations

from typing import Any, Generic, TypeVar

import jax
from flax import struct

from fl4health_tpu.core.types import Params

S = TypeVar("S")


@struct.dataclass
class FitResults:
    """Stacked results of one fit round — what aggregate() consumes.

    packets:       client-stacked payload pytree (params or richer packet)
    sample_counts: [clients] train-set sizes
    train_losses:  dict of [clients] scalars from local training meters
    train_metrics: dict of [clients] metric values
    mask:          [clients] 1.0 = participated this round
    """

    packets: Any
    sample_counts: jax.Array
    train_losses: Any
    train_metrics: Any
    mask: jax.Array


def replace_global_params(strategy: "Strategy", server_state: Any, params) -> Any:
    """``server_state`` with the innermost strategy's params replaced,
    through any wrapper nesting (CompressingStrategy, QuarantiningStrategy,
    ... — wrappers expose ``.inner`` on both the strategy and its state).
    The direct ``state.replace(params=...)`` only works on unwrapped
    states; every params-installation path (checkpoint import, evaluate
    server hydration) must go through this instead."""
    own = getattr(strategy, "replace_global_params", None)
    if own is not None:
        # a wrapper that keeps part of the model outside its inner state
        # (strategies/shared_base.py) installs a whole-model tree itself
        return own(server_state, params)
    if hasattr(strategy, "inner") and hasattr(server_state, "inner"):
        return server_state.replace(inner=replace_global_params(
            strategy.inner, server_state.inner, params
        ))
    return server_state.replace(params=params)


def inner_state_sharding_spec(inner: "Strategy", server_state: Any,
                              clients_axis: str):
    """Delegate ``state_sharding_spec`` to a wrapped strategy for use
    inside a wrapper's own spec pytree. A wrapper state embeds the inner
    SPEC tree, so the inner strategy's "no preference" (no hook, or the
    hook returning None) must become an explicit replicate-everything
    ``P()`` leaf rather than None — None would read as "no spec for this
    subtree" and mis-shard the wrapper state."""
    from jax.sharding import PartitionSpec as P

    hook = getattr(inner, "state_sharding_spec", None)
    spec = hook(server_state, clients_axis) if hook else None
    return P() if spec is None else spec


class Strategy:
    """Base protocol. Subclasses override any of the four methods.

    All methods must be jit-traceable (no data-dependent Python control flow).
    """

    weighted_aggregation: bool = True
    weighted_eval_aggregation: bool = True

    def bind_client_manager(self, client_manager: Any) -> None:
        """Setup-time hook: FederatedSimulation calls this with its client
        manager before training so a strategy can derive/validate sampling
        assumptions (e.g. DP-FedAvgM's ``fraction_fit`` against the
        manager's sampling fraction). Runs host-side once; default no-op."""

    def init(self, params: Params) -> Any:
        """Build initial server state from initial model params."""
        raise NotImplementedError

    def state_sharding_spec(self, server_state: Any, clients_axis: str):
        """Optional per-leaf ``PartitionSpec`` pytree (prefix) for the
        server state on a client mesh; ``None`` = fully replicated.

        Strategies whose state carries per-client ``[C, ...]`` leaves
        (wrapper bookkeeping, EF residuals) or replica-sharded optimizer
        vectors (the ZeRO-1 server optimizer) override this so the round
        program's ``in_shardings``/``out_shardings`` keep those leaves
        split instead of replicating the whole state
        (``parallel/program.py RoundProgramBuilder``)."""
        return None

    def state_rows(self, server_state: Any) -> Any:
        """Per-client rows of the server state: a pytree whose every leaf
        carries a leading ``[C]`` client axis (wrapper bookkeeping like
        quarantine strikes, error-feedback residuals), or ``None`` when
        the strategy keeps no per-client server state.

        Cohort-slot execution (``server/registry.py``) gathers these rows
        for the sampled cohort into fixed ``[K]`` slot tensors before each
        round and scatters the updated rows back into the host registry
        afterwards. Strategies exposing rows MUST (a) initialize every
        client's row identically in ``init`` (client-symmetric start — the
        registry derives un-touched clients' rows from one prototype) and
        (b) keep client ``i``'s row a function of client ``i``'s
        participation only. Wrapper strategies compose by embedding the
        inner strategy's rows under an ``"inner"`` key; state-passthrough
        wrappers (``FedBuff``, whose state IS the inner state) delegate
        wholesale."""
        return None

    def scatter_state_rows(self, server_state: Any, rows: Any) -> Any:
        """Inverse of :meth:`state_rows`: the server state with its
        per-client rows replaced by ``rows`` (the same structure
        ``state_rows`` returned, leaves re-gathered to a new leading
        axis). Must be pure tree surgery — no math — so gather/scatter
        round-trips bit-identically."""
        if jax.tree_util.tree_leaves(rows):
            raise ValueError(
                f"{type(self).__name__} has no per-client state rows to "
                "scatter into (state_rows() is None)"
            )
        return server_state

    def global_params(self, server_state: Any) -> Params:
        """The current global model params (for checkpointing/eval)."""
        return server_state.params

    def divergence_reference(self, server_state: Any) -> Params:
        """Reference point for the in-graph weight-divergence telemetry
        (observability/telemetry.py): each client stack's l2 distance is
        measured from THIS tree after aggregation. Default: the aggregated
        global model. Strategies whose broadcast differs from their stored
        globals (e.g. a server-momentum strategy whose payload folds in the
        momentum step) may override so divergence measures distance from
        what clients will actually pull next round. Jit-traceable."""
        return self.global_params(server_state)

    def client_payload(self, server_state: Any, round_idx: jax.Array) -> Any:
        """What is broadcast to clients this round (configure_fit's parameters)."""
        return server_state.params

    def aggregate(self, server_state: Any, results: FitResults, round_idx: jax.Array) -> Any:
        """aggregate_fit: consume stacked packets, produce new server state."""
        raise NotImplementedError

    def update_after_eval(
        self,
        server_state: Any,
        eval_losses: Any,
        eval_metrics: Any,
        mask: jax.Array,
    ) -> Any:
        """Consume per-client post-aggregation eval results ([clients] arrays).

        Needed by strategies whose next-round weights depend on evaluation of
        the aggregated model (FedDG-GA's generalization gaps,
        strategies/feddg_ga.py:382 update_weights_by_ga). Default: no-op.
        """
        return server_state
