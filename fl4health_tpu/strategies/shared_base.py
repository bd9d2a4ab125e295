"""Parameters that exist once — the server-side half of the shared/per-client
split (``clients/engine.py ModelDef.per_client``).

Parity: /root/reference/examples/fedllm_example trains LoRA adapters
federally over a frozen base that every client loads once; only the
adapters cross the wire.

``SharedBaseStrategy`` wraps any strategy. Its state holds the inner
strategy's state, built over the per-client leaves alone, and the shared
leaves once. The inner strategy therefore never sees a shared leaf: not in
``client_payload``, not in ``aggregate``, not in its optimizer moments.
``global_params`` is the union, so checkpoints, ``sim.global_params`` and
``set_global_params`` keep speaking of whole models; the shared leaves come
back bit for bit as they were installed, because nothing computes on them
here. ``FederatedSimulation`` wraps the strategy itself when the logic's
model declares shared leaves.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from flax import struct

from fl4health_tpu.core.pytree import merge_trees, split_by_path
from fl4health_tpu.core.types import Params
from fl4health_tpu.strategies.base import (FitResults, Strategy,
                                           inner_state_sharding_spec,
                                           replace_global_params)


@struct.dataclass
class SharedBaseState:
    inner: Any
    shared: Params  # the leaves outside the per-client predicate, once


class SharedBaseStrategy(Strategy):
    def __init__(self, inner: Strategy, per_client: Callable[[str], bool]):
        self.inner = inner
        self.per_client = per_client
        self.weighted_aggregation = inner.weighted_aggregation
        self.weighted_eval_aggregation = inner.weighted_eval_aggregation
        self.evaluate_after_fit = bool(getattr(inner, "evaluate_after_fit",
                                               False))
        # chunk-eligibility passthrough, as compression/strategy.py has it
        overrides = getattr(inner, "overrides_update_after_eval", None)
        if overrides is None:
            overrides = (type(inner).update_after_eval
                         is not Strategy.update_after_eval)
        self.overrides_update_after_eval = overrides

    def split(self, params: Params) -> tuple[Params, Params]:
        """(per-client leaves, shared leaves) of a whole-model tree."""
        return split_by_path(params, self.per_client)

    def bind_client_manager(self, client_manager: Any) -> None:
        self.inner.bind_client_manager(client_manager)

    def init(self, params: Params) -> SharedBaseState:
        per_client, shared = self.split(params)
        return self.init_split(per_client, shared)

    def init_split(self, per_client: Params, shared: Params) -> SharedBaseState:
        return SharedBaseState(inner=self.inner.init(per_client), shared=shared)

    def shared_params(self, server_state: SharedBaseState) -> Params:
        return server_state.shared

    def global_params(self, server_state: SharedBaseState) -> Params:
        return merge_trees(server_state.shared,
                           self.inner.global_params(server_state.inner))

    def replace_global_params(self, server_state: SharedBaseState,
                              params: Params) -> SharedBaseState:
        """A whole-model tree in: the per-client leaves go to the inner
        strategy's state, the shared ones replace the held ones as they are
        (no copy, no cast)."""
        per_client, shared = self.split(params)
        return SharedBaseState(
            inner=replace_global_params(self.inner, server_state.inner,
                                        per_client),
            shared=shared,
        )

    def divergence_reference(self, server_state: SharedBaseState) -> Params:
        return self.inner.divergence_reference(server_state.inner)

    def client_payload(self, server_state: SharedBaseState, round_idx) -> Any:
        return self.inner.client_payload(server_state.inner, round_idx)

    def aggregate(self, server_state: SharedBaseState, results: FitResults,
                  round_idx) -> SharedBaseState:
        return server_state.replace(
            inner=self.inner.aggregate(server_state.inner, results, round_idx)
        )

    def update_after_eval(self, server_state: SharedBaseState, eval_losses,
                          eval_metrics, mask) -> SharedBaseState:
        return server_state.replace(inner=self.inner.update_after_eval(
            server_state.inner, eval_losses, eval_metrics, mask))

    def state_sharding_spec(self, server_state: SharedBaseState,
                            clients_axis: str):
        from jax.sharding import PartitionSpec as P

        return SharedBaseState(
            inner=inner_state_sharding_spec(self.inner, server_state.inner,
                                            clients_axis),
            shared=P(),
        )

    def state_rows(self, server_state: SharedBaseState) -> Any:
        return self.inner.state_rows(server_state.inner)

    def scatter_state_rows(self, server_state: SharedBaseState,
                           rows: Any) -> SharedBaseState:
        return server_state.replace(
            inner=self.inner.scatter_state_rows(server_state.inner, rows))


def materialized(server_state: Any) -> bool:
    """False while a SharedBaseState still holds its shared leaves as
    ``ShapeDtypeStruct``s (a freshly built simulation, before its first
    ``fit()`` or ``set_global_params``)."""
    shared = getattr(server_state, "shared", None)
    return not any(isinstance(leaf, jax.ShapeDtypeStruct)
                   for leaf in jax.tree_util.tree_leaves(shared))
