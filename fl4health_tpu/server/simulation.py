"""FederatedSimulation — the round loop (FlServer.fit equivalent), SPMD-style.

Reference control flow (/root/reference/fl4health/servers/base_server.py:232
FlServer.fit -> fit_round :278 -> strategy.configure_fit -> gRPC fan-out ->
strategy.aggregate_fit -> evaluate_round :357): one server process and N
client processes exchanging serialized NumPy arrays.

TPU-native re-design: the N simulated clients are one client-stacked
``TrainState`` (leading [clients] axis on every leaf, shardable over a
``clients`` mesh axis). One round compiles to two programs:

    fit_round  = pull(payload) -> vmap(local_train scan) -> push -> aggregate
    eval_round = pull(global)  -> vmap(local_eval scan)  -> metric aggregation

The Python loop over rounds only moves host-side concerns: batch construction,
sampling, reporting, checkpointing — matching the reference's split of
responsibilities without any per-round serialize/deserialize.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import sys
import threading
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fl4health_tpu.checkpointing.async_writer import AsyncCheckpointWriter
from fl4health_tpu.checkpointing.checkpointer import CheckpointMode
from fl4health_tpu.clients import engine
from fl4health_tpu.observability import Observability
from fl4health_tpu.observability import device_specs
from fl4health_tpu.observability import stages as stage_attr
from fl4health_tpu.observability import telemetry as telem
from fl4health_tpu.observability.flightrec import trap_sigterm
from fl4health_tpu.observability.manifest import config_hash, run_manifest
from fl4health_tpu.observability.telemetry import RoundTelemetry
from fl4health_tpu.clients.engine import Batch, ClientLogic, TrainState
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.exchange.exchanger import FullExchanger
from fl4health_tpu.metrics.aggregation import aggregate_metrics
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.parallel.program import (
    CLIENTS_AXIS,
    MeshConfig,
    RoundProgramBuilder,
)
from fl4health_tpu.server.client_manager import ClientManager, FullParticipationManager
from fl4health_tpu.server.pipeline import RoundConsumer, RoundPrefetcher
from fl4health_tpu.server.registry import (
    ClientRegistry,
    CohortConfig,
    _SlotManagerView,
    as_registry_source,
)
from fl4health_tpu.strategies.base import FitResults, Strategy

# Execution modes fit() can run in (reported through observability and every
# reporter's fit_start payload):
# - "pipelined_per_round": one fit + one eval dispatch per round, with the
#   host epilogue (failure policy, checkpointing, records, reporting) running
#   in a background RoundConsumer and the next round's batches prefetched —
#   host work overlaps device execution.
# - "chunked_scan": ALL rounds compile into one on-device lax.scan dispatch
#   (fit + eval per round inside the scan); per-round host work collapses to
#   a single fused device->host pull at the end.
EXEC_PIPELINED = "pipelined_per_round"
EXEC_CHUNKED = "chunked_scan"


def _donate_argnums(*argnums: int) -> tuple[int, ...]:
    """Buffer donation, gated OFF the CPU backend.

    The gate was written against an older jaxlib (XLA:CPU with the
    persistent compilation cache of tests/conftest.py): an executable
    compiled WITH input-output aliasing computed correct results on the
    compile run but WRONG numerics after being reloaded from the
    persistent cache. Whether jax 0.9.0 still does that is UNVERIFIED —
    the gate stays until it is re-tested (ROADMAP D10). Donation on CPU
    saves nothing we need — the in-place client-stack update is a
    device-memory lever — so CPU runs plain and the TPU gets the donation
    (exercised on a v5e by chip_smoke.py, with a cold and a warm cache).
    (One implementation: RoundProgramBuilder.donate — the sharded programs
    route through the same gate.)"""
    return RoundProgramBuilder.donate(*argnums)


def _dedupe_donated(*trees):
    """Break buffer aliasing inside trees about to be DONATED.

    XLA rejects donating the same buffer twice (``f(donate(a), donate(a))``)
    and Python-level state construction can legitimately alias — e.g. a
    strategy ``init`` storing the initial params in two fields. Compiled
    round OUTPUTS never alias (each output gets its own buffer), so one
    dedupe at fit entry keeps every subsequent donated dispatch safe.
    Returns the trees with later duplicates replaced by copies."""
    seen: set = set()

    def fix(x):
        if not isinstance(x, jax.Array):
            return x
        try:
            key = x.unsafe_buffer_pointer()
        except Exception:  # sharded/committed arrays: object identity
            key = id(x)
        if key in seen:
            return jnp.copy(x)
        seen.add(key)
        return x

    return jax.tree_util.tree_map(fix, trees)


@dataclasses.dataclass
class ClientDataset:
    """Host-side per-client data (the DataLoader boundary).

    ``x_*`` may be a plain array or a PYTREE of arrays sharing axis 0 (dict
    inputs — the reference's DictionaryDataset role); the engine's stacked
    gather handles either, and the model's ``__call__`` receives whatever
    structure was provided."""

    x_train: Any
    y_train: Any
    x_val: Any
    y_val: Any
    x_test: Any = None
    y_test: Any = None

    @property
    def n_train(self) -> int:
        return engine.data_rows(self.x_train)


class ClientFailuresError(RuntimeError):
    """Raised when accept_failures=False and client failures occur
    (base_server.py:443-451).

    Structured for the postmortem verdict: ``clients`` (failing client
    indices, slot positions under cohort execution), ``round`` and
    ``registry_clients`` (cohort rounds only — the slots mapped to
    registry ids) are attached by the round epilogue before the raise
    unwinds ``fit()``."""

    def __init__(self, message: str, clients: Sequence[int] = ()):
        super().__init__(message)
        self.clients = [int(c) for c in clients]
        self.round: int | None = None
        self.registry_clients: list[int] | None = None


@dataclasses.dataclass
class FailurePolicy:
    """accept_failures semantics (base_server.py:104,316-318): with
    ``accept_failures=False`` any failed client terminates the run. The SPMD
    failure signal is a non-finite backward loss in a participating client's
    row of the stacked results (a crashed gRPC peer has no in-process
    equivalent; a NaN-poisoned shard is the analogous failure mode)."""

    accept_failures: bool = True

    def check(self, per_client_losses, mask) -> list[int]:
        key = "backward" if "backward" in per_client_losses else None
        if key is None:
            return []
        # pure numpy: the pipelined loop runs this on already-host data in a
        # background thread — the screen must not dispatch device work
        row = np.asarray(per_client_losses[key])
        bad = np.logical_and(~np.isfinite(row), np.asarray(mask) > 0)
        failed = [int(i) for i in np.nonzero(bad)[0]]
        for cid in failed:
            logging.getLogger(__name__).error(
                "Client %d failed (non-finite training loss).", cid
            )
        if failed and not self.accept_failures:
            raise ClientFailuresError(
                f"The server encountered failures from clients {failed} and "
                "accept_failures is set to False",
                clients=failed,
            )
        return failed


@dataclasses.dataclass
class RoundRecord:
    round: int
    fit_losses: dict
    fit_metrics: dict
    eval_losses: dict
    eval_metrics: dict
    fit_elapsed_s: float
    eval_elapsed_s: float


@dataclasses.dataclass
class _RoundWork:
    """Everything the RoundConsumer needs to finish one round on the host.

    ``device_results`` holds fresh (never-donated) device arrays — round
    results plus any ``_pre_agg_params``/``_post_agg_params``/
    ``_state_trees`` device-side snapshot copies — and the consumer performs
    the round's single fused device->host transfer of all of it."""

    round: int
    device_results: dict
    fit_elapsed_s: float
    eval_elapsed_s: float
    device_wait_s: float
    compiles_before: float
    compile_s_before: float
    compiles_after: float | None
    compile_s_after: float | None
    # buffered-async runs only: host facts of the consumed buffer-fill
    # event (staleness stats, virtual cadence) from the static plan —
    # merged into the round record/metrics by the consumer
    async_info: dict | None = None
    # async checkpoint extras: the plan-prefix fingerprint + virtual clock
    # stored with the event's state snapshot (None on sync rounds)
    resume_meta: dict | None = None
    # cohort-slot rounds only: the round's sampled registry ids, valid
    # count, staging wall and the scatter-completion event the producer
    # gates the next state gather on (None on dense rounds)
    cohort_meta: dict | None = None
    # pre-built cohort summary for rounds whose registry exchange the
    # PRODUCER already performed (async-over-registry events) — the
    # consumer then has no ``_registry_rows`` to scatter but still reports
    # the cohort facts
    cohort_info: dict | None = None


class FederatedSimulation:
    """Couples logic + optimizer + strategy + data into a runnable FL job."""

    def __init__(
        self,
        logic: ClientLogic,
        tx: optax.GradientTransformation,
        strategy: Strategy,
        datasets: Sequence[ClientDataset],
        batch_size: int,
        metrics: MetricManager,
        local_epochs: int | None = None,
        local_steps: int | None = None,
        exchanger=None,
        client_manager: ClientManager | None = None,
        seed: int = 42,
        extra_loss_keys: tuple[str, ...] = (),
        eval_loss_keys: tuple[str, ...] = (),
        reporters: Sequence[Any] = (),
        model_checkpointers: Sequence[tuple[Any, Any]] = (),
        state_checkpointer: Any = None,
        early_stopping: engine.EarlyStoppingConfig | None = None,
        flash_early_stopping: Any = None,
        failure_policy: FailurePolicy | None = None,
        profile_dir: str | None = None,
        train_data_provider: Any = None,
        observability: Observability | None = None,
        execution_mode: str = "auto",
        pipeline_depth: int = 2,
        fault_plan: Any = None,
        compression: Any = None,
        mesh: MeshConfig | None = None,
        precision: Any = None,
        async_config: Any = None,
        cohort: CohortConfig | None = None,
        recovery: Any = None,
    ):
        if (local_epochs is None) == (local_steps is None):
            raise ValueError("specify exactly one of local_epochs / local_steps "
                             "(reference: utils/config.py epochs-xor-steps check)")
        if execution_mode not in ("auto", "pipelined", "chunked"):
            raise ValueError(
                f"execution_mode must be 'auto', 'pipelined' or 'chunked'; "
                f"got {execution_mode!r}"
            )
        # Cohort-slot execution (server/registry.py CohortConfig): rounds
        # compile and run against a fixed [slots] axis while the client
        # population lives in a host-resident ClientRegistry — HBM and
        # per-round FLOPs scale with the SAMPLED cohort, not the registry.
        # None (the default) keeps the dense [n_clients] path bit-identical
        # to pre-cohort builds on both execution modes.
        if cohort is not None and not isinstance(cohort, CohortConfig):
            raise TypeError(
                "cohort must be a CohortConfig (or None); got "
                f"{type(cohort).__name__} — pass server.registry.CohortConfig"
            )
        self.cohort_config = cohort
        self._cohort_active = cohort is not None
        self.registry: ClientRegistry | None = None
        if self._cohort_active:
            source = as_registry_source(datasets)
            self.registry = ClientRegistry(
                source, batch_size, local_steps, local_epochs
            )
            self.registry_size = source.n_clients
            # every compiled shape below is SLOT-shaped; the registry keeps
            # the O(N) facts (sizes, rows, data) host-side
            self.datasets = []
            self.n_clients = cohort.slots
        else:
            self.registry_size = None
            self.datasets = list(datasets)
            self.n_clients = len(self.datasets)
        self.logic = logic
        self.tx = tx
        self.strategy = strategy
        self.batch_size = batch_size
        self.metrics = metrics
        self._extra_loss_keys = tuple(extra_loss_keys)
        self._eval_loss_keys = tuple(eval_loss_keys)
        self.local_epochs = local_epochs
        self.local_steps = local_steps
        self.exchanger = exchanger or FullExchanger()
        # Parameters that exist once (clients/engine.py ModelDef.per_client,
        # strategies/shared_base.py): a model that declares shared leaves
        # keeps them once, in the server state; client states, optimizer,
        # exchanger and the inner strategy see the per-client leaves alone.
        # A model without the predicate (every leaf per client) builds the
        # exact programs it always did.
        self._per_client = logic.model.per_client
        if self._per_client is not None:
            unsupported = {
                "cohort": cohort, "async_config": async_config, "mesh": mesh,
                "compression": compression, "early_stopping": early_stopping,
                "flash_early_stopping": flash_early_stopping,
            }
            on = [k for k, v in unsupported.items() if v is not None]
            if on:
                raise NotImplementedError(
                    "a model with shared parameters (ModelDef.per_client) "
                    f"runs the synchronous round programs only; got {on}"
                )
            from fl4health_tpu.strategies.shared_base import SharedBaseStrategy

            strategy = self.strategy = SharedBaseStrategy(
                strategy, self._per_client
            )
        # Compressed exchange (compression/: CompressionConfig): the lossy
        # client->server channel compiles INTO the round programs via a
        # CompressingStrategy wrapper, so chunked mode keeps one dispatch
        # per N rounds and both execution modes draw identical stochastic
        # codes. None (or a config with no lossy stage) wraps nothing —
        # trajectories stay bit-identical to an uncompressed build.
        self.compression = compression
        if compression is not None:
            from fl4health_tpu.compression.config import CompressionConfig

            if not isinstance(compression, CompressionConfig):
                raise TypeError(
                    "compression must be a CompressionConfig (or None); got "
                    f"{type(compression).__name__} — a duck-typed config "
                    "would silently train uncompressed"
                )
        self._compression_active = bool(
            compression is not None and compression.enabled
        )
        self._wire_bytes_cache: int | None = None
        if self._compression_active:
            from fl4health_tpu.exchange.exchanger import FixedLayerExchanger

            if (getattr(self.exchanger, "wants_packet_payload", False)
                    or isinstance(self.exchanger, FixedLayerExchanger)):
                # FixedLayerExchanger (FedBN et al.) zeroes non-exchanged
                # leaves in push(), so each would read as a huge fake
                # -reference delta dominating the top-k and poisoning the
                # EF residual — reject it like the packet-shaped partials
                raise ValueError(
                    "compression composes with full-model exchange only: "
                    f"{type(self.exchanger).__name__} ships partial "
                    "payloads whose zeroed/masked entries would read as "
                    "real deltas (it is already a compression scheme)"
                )
            from fl4health_tpu.compression.strategy import CompressingStrategy

            strategy = self.strategy = CompressingStrategy(
                strategy, compression
            )
        # Buffered-async federation (server/async_schedule.py AsyncConfig):
        # the FedBuff-style mode where the server aggregates as soon as a
        # buffer of K updates arrives, staleness-discounting stale ones.
        # The schedule resolves to a STATIC event plan at fit() time, so
        # async runs still execute as compiled round programs on both
        # execution paths. None (the default) builds the exact synchronous
        # programs — trajectories bit-identical to pre-async builds.
        self.async_config = async_config
        if async_config is not None:
            from fl4health_tpu.server.async_schedule import AsyncConfig

            if not isinstance(async_config, AsyncConfig):
                raise TypeError(
                    "async_config must be an AsyncConfig (or None); got "
                    f"{type(async_config).__name__} — a duck-typed config "
                    "would silently train synchronously"
                )
            if self._cohort_active:
                # FedBuff over the registry: K slots hold seated registry
                # clients, so the buffer fills from the SLOTS, and the
                # static seating plan needs an occupant per seat
                if async_config.buffer_size > self.cohort_config.slots:
                    raise ValueError(
                        f"async_config.buffer_size="
                        f"{async_config.buffer_size} exceeds the cohort "
                        f"slots ({self.cohort_config.slots}): the buffer "
                        "fills from the seated slots, so it could never "
                        "fill"
                    )
            elif async_config.buffer_size > len(datasets):
                raise ValueError(
                    f"async_config.buffer_size={async_config.buffer_size} "
                    f"exceeds the cohort ({len(datasets)} clients): the "
                    "buffer could never fill"
                )
            from fl4health_tpu.strategies.fedbuff import FedBuff

            if isinstance(strategy, FedBuff):
                # A pre-wrapped FedBuff must AGREE with the AsyncConfig:
                # the manifest hashes the config's staleness parameters,
                # so a wrapper silently discounting with different ones
                # would misattribute the experiment.
                if (strategy.staleness_exponent
                        != float(async_config.staleness_exponent)
                        or strategy.max_staleness
                        != async_config.max_staleness):
                    raise ValueError(
                        "the provided FedBuff wrapper's staleness "
                        f"parameters (exponent={strategy.staleness_exponent}"
                        f", max_staleness={strategy.max_staleness}) differ "
                        "from async_config's "
                        f"(exponent={async_config.staleness_exponent}, "
                        f"max_staleness={async_config.max_staleness}) — "
                        "the manifest records the config's values, so "
                        "they must match (simplest: pass the bare inner "
                        "strategy and let async_config do the wrapping)"
                    )
            else:
                # FedBuff must be the OUTERMOST wrapper: the async round
                # programs call its async_aggregation_mask hook, and inner
                # wrappers (compression/quarantine) see the discounted
                # fractional mask exactly like a sampled one
                strategy = self.strategy = FedBuff(
                    strategy,
                    staleness_exponent=async_config.staleness_exponent,
                    max_staleness=async_config.max_staleness,
                )
        self._async_active = async_config is not None
        # Self-healing recovery (resilience/supervisor.py): recovery=
        # RecoveryPolicy(...) routes fit() through a RecoverySupervisor
        # that turns the structured abnormal-end taxonomy (watchdog halt,
        # client failures, quorum loss, corrupt checkpoints) into
        # rollback-quarantine-resume per a declarative escalation ladder.
        # None (the default) keeps fit() exactly the unsupervised loop —
        # and an armed-but-never-engaged policy is pinned bit-identical
        # too (the supervisor's hooks are no-ops until it engages).
        self.recovery_policy = recovery
        if recovery is not None:
            from fl4health_tpu.resilience.supervisor import RecoveryPolicy

            if not isinstance(recovery, RecoveryPolicy):
                raise TypeError(
                    "recovery must be a RecoveryPolicy (or None); got "
                    f"{type(recovery).__name__} — pass "
                    "resilience.supervisor.RecoveryPolicy"
                )
        self._recovery_supervisor = None
        # Device-mesh placement (parallel/program.py): mesh=None keeps the
        # single-chip programs (and trajectories) bit-identical; a
        # MeshConfig shards the [C, ...] client axes over the "clients"
        # mesh axis in every compiled round program, replicates (or
        # ZeRO-1-shards) the server state, and stages per-round data with
        # sharded device_put — massive cohorts across data-parallel chips.
        if mesh is not None and not isinstance(mesh, MeshConfig):
            raise TypeError(
                "mesh must be a MeshConfig (or None); got "
                f"{type(mesh).__name__} — pass parallel.program.MeshConfig"
            )
        self.mesh_config = mesh
        self._program_builder = RoundProgramBuilder(
            mesh, n_clients=self.n_clients
        )
        # Engine-level mixed precision (precision/: PrecisionConfig): the
        # compute-dtype cast and fp16 loss scaling compile INTO the round
        # programs at model-apply time — every client algorithm trains
        # bf16/fp16 against the f32 master weights this simulation carries,
        # and everything pinned on those masters (DP clip->noise, telemetry
        # norms, compression deltas, robust aggregation, ZeRO-1 server
        # shards) stays f32. None (or an inactive f32 config) builds the
        # exact pre-precision programs — trajectories bit-identical on both
        # execution modes (tests/precision/).
        if precision is not None:
            from fl4health_tpu.precision import PrecisionConfig

            if not isinstance(precision, PrecisionConfig):
                raise TypeError(
                    "precision must be a PrecisionConfig (or None); got "
                    f"{type(precision).__name__} — a duck-typed config "
                    "would silently train in f32"
                )
        self.precision = precision
        self._precision_active = bool(
            precision is not None and precision.active
        )
        self._precision_scaling = bool(
            precision is not None and precision.scaling_active
        )
        if self._cohort_active:
            # the manager samples over the REGISTRY; the compiled programs
            # are slot-shaped
            self.client_manager = client_manager or FullParticipationManager(
                self.registry_size
            )
            if self.client_manager.n_clients != self.registry_size:
                raise ValueError(
                    f"client_manager covers {self.client_manager.n_clients} "
                    f"clients but the registry holds {self.registry_size}; "
                    "the sampling manager must be built over the registry"
                )
            if (isinstance(self.client_manager, FullParticipationManager)
                    and self.cohort_config.slots < self.registry_size
                    and not self._async_active):
                # (buffered-async over the registry seats K of N clients
                # per the occupancy plan — full participation there means
                # "every SEATED slot", so slots < N is the normal shape)
                raise ValueError(
                    f"full participation needs slots >= registry size "
                    f"({self.registry_size}); got slots="
                    f"{self.cohort_config.slots} — pass a sampling manager "
                    "(FixedFractionManager/PoissonSamplingManager) whose "
                    "worst-case draw fits the slots"
                )
        else:
            self.client_manager = client_manager or FullParticipationManager(
                self.n_clients
            )
        # setup-time strategy <-> sampling-scheme validation (e.g. the DP
        # strategies derive/check fraction_fit against the manager's sampling
        # fraction — a mismatch silently mis-scales the DP noise).
        bind = getattr(strategy, "bind_client_manager", None)
        if bind is not None:
            bind(self.client_manager)
        if self._cohort_active and bind is not None:
            # re-bind a SLOT-COUNT view so wrapper strategies size their
            # per-client server rows [slots] — the compiled shape; the
            # registry persists the O(N) rows host-side. The view delegates
            # fraction/min_clients, so the validation above still saw the
            # true scheme.
            bind(_SlotManagerView(self.client_manager,
                                  self.cohort_config.slots))
        self.reporters = list(reporters)
        # (CheckpointMode, ParamsCheckpointer) pairs — PRE_AGGREGATION fires on
        # the client-stacked post-fit params, POST_AGGREGATION on the
        # aggregated global model (client_module.py:23-28 semantics).
        self.model_checkpointers = list(model_checkpointers)
        self.state_checkpointer = state_checkpointer
        self.early_stopping = early_stopping
        self.flash_early_stopping = flash_early_stopping
        if flash_early_stopping is not None:
            # Flash is epoch-defined (flash_client.py:71-95 rejects step-wise)
            if local_epochs is None:
                raise ValueError("flash_early_stopping requires local_epochs")
            if early_stopping is not None:
                raise ValueError("flash_early_stopping and early_stopping are exclusive")
            if flash_early_stopping.n_epochs != local_epochs:
                raise ValueError(
                    f"flash_early_stopping.n_epochs={flash_early_stopping.n_epochs} "
                    f"must equal local_epochs={local_epochs}: the gamma rule is "
                    "defined per true local epoch"
                )
        self.failure_policy = failure_policy or FailurePolicy()
        # SURVEY §5: the reference records only coarse wall-clock timings;
        # a real device-level trace is the strictly-better TPU-native story.
        # When set, fit() wraps the round loop in jax.profiler.trace and the
        # trace directory can be opened in TensorBoard/XProf.
        self.profile_dir = profile_dir
        # Round-level observability (observability/__init__.py): spans per
        # round phase, compile/byte counters, opt-in per-round XProf capture.
        # Defaults to a disabled handle whose every hook is a shared no-op,
        # so the un-instrumented hot loop stays exactly as fast (and adds no
        # device syncs — the fence is a pass-through when disabled).
        self.observability = observability or Observability(enabled=False)
        self._payload_bytes_cache: tuple[int, int] | None = None
        # Optional per-round host data refresh: callable(round_idx) ->
        # (x_list, y_list) | None. Called at the top of each fit() round;
        # shapes must match the originals so the compiled round program
        # stays valid (no recompile). The nnU-Net pipeline uses this for
        # fresh patch extraction per round (nnunet.data.make_patch_resampler);
        # fit_chunk bakes its data at dispatch time and bypasses it.
        self.train_data_provider = train_data_provider
        if self._async_active:
            # The async event programs are FUSED (aggregate -> eval ->
            # retrain in one dispatch), so hooks that need the host mid-
            # round cannot compose; and participation is DERIVED from the
            # arrival schedule, so a sampling manager would be silently
            # ignored. Reject loudly instead.
            if not isinstance(self.client_manager, FullParticipationManager):
                raise ValueError(
                    "async_config derives participation from the buffer's "
                    "arrival schedule; a sampling client manager "
                    f"({type(self.client_manager).__name__}) is not "
                    "composable with buffered-async mode"
                )
            overrides = getattr(
                self.strategy, "overrides_update_after_eval", None
            )
            if overrides is None:
                overrides = (type(self.strategy).update_after_eval
                             is not Strategy.update_after_eval)
            if overrides:
                raise ValueError(
                    "async_config is not composable with strategies that "
                    "consume per-round eval results on the host "
                    "(update_after_eval override): the async event "
                    "program fuses aggregate+eval+retrain in one dispatch"
                )
            if self.train_data_provider is not None:
                raise ValueError(
                    "async_config is not composable with "
                    "train_data_provider: the async event programs bake "
                    "their data at dispatch time"
                )
            if self.model_checkpointers:
                raise ValueError(
                    "async_config is not composable with per-round model "
                    "checkpointing: there is no synchronous post-fit/"
                    "pre-aggregation moment inside a fused buffer-fill "
                    "event (state checkpointing — resume — composes; use "
                    "state_checkpointer)"
                )
            if self._cohort_active and self.mesh_config is not None:
                raise ValueError(
                    "async_config + cohort=CohortConfig(...) does not yet "
                    "compose with mesh: the per-event occupancy swap "
                    "restages seated rows host-side, which would fight the "
                    "mesh's sharded staging; run the composition unsharded "
                    "or drop one of the two"
                )
            if self._cohort_active and self.state_checkpointer is not None:
                raise ValueError(
                    "async_config + cohort=CohortConfig(...) does not yet "
                    "compose with state checkpointing: a resume would need "
                    "a frame persisting BOTH the pending update buffer and "
                    "the registry's dirty rows + seating cursor, and no "
                    "such combined frame format exists yet"
                )
            sc = self.state_checkpointer
            if sc is not None and not (
                hasattr(sc, "save_async_snapshot")
                and hasattr(sc, "load_async_simulation")
            ):
                raise ValueError(
                    "async state checkpointing needs a checkpointer that "
                    "can snapshot the pending update buffer and the event "
                    "cursor (save_async_snapshot/load_async_simulation — "
                    f"SimulationStateCheckpointer); {type(sc).__name__} "
                    "cannot, so an interrupted async run could not resume "
                    "mid-plan"
                )
        if self._cohort_active:
            # cohort-slot composition rules: the slot round evaluates the
            # SAMPLED cohort, so hooks that consume whole-population
            # per-round eval on the host cannot compose; per-round host
            # data refresh would invalidate the registry's staging.
            overrides = getattr(
                self.strategy, "overrides_update_after_eval", None
            )
            if overrides is None:
                overrides = (type(self.strategy).update_after_eval
                             is not Strategy.update_after_eval)
            if overrides:
                raise ValueError(
                    "cohort=CohortConfig(...) is not composable with "
                    "strategies that consume per-round eval results on the "
                    "host (update_after_eval override): slot eval covers "
                    "the sampled cohort, not the population"
                )
            if self.train_data_provider is not None:
                raise ValueError(
                    "cohort=CohortConfig(...) is not composable with "
                    "train_data_provider: per-round data lives in the "
                    "registry source — refresh it there"
                )
            sc = self.state_checkpointer
            if sc is not None and not (
                hasattr(sc, "save_cohort_snapshot")
                and hasattr(sc, "load_cohort_simulation")
            ):
                raise ValueError(
                    "cohort state checkpointing needs a checkpointer that "
                    "persists the registry's dirty rows (save_cohort_"
                    "snapshot/load_cohort_simulation — "
                    f"SimulationStateCheckpointer); {type(sc).__name__} "
                    "cannot, so an interrupted cohort run could not resume"
                )
        # fit() dispatch strategy: "auto" routes through the on-device
        # multi-round chunked scan whenever the configuration permits (see
        # _chunk_ineligibility) and falls back to the pipelined per-round
        # path otherwise; "pipelined"/"chunked" force one path (forcing
        # "chunked" on an ineligible config raises at fit()).
        self.execution_mode = execution_mode
        # How many rounds of host epilogue work may be in flight behind the
        # device on the pipelined path (bounded RoundConsumer queue).
        self.pipeline_depth = pipeline_depth
        # Deterministic chaos layer (resilience/faults.py FaultPlan): client
        # dropout multiplies the participation mask and update corruption
        # transforms the packet stack INSIDE the round programs, so the same
        # plan injects the same faults on both execution modes and a faulted
        # run never recompiles. None (or an empty plan) leaves the round
        # closures untouched — trajectories stay bit-identical.
        self._fault_plan = fault_plan
        # host mirror of the in-graph quarantine mask (strategy-driven), for
        # entered/released transition accounting in the per-round metrics
        self._last_quarantine: list[int] | None = None
        # cohort-slot runs: persistent registry-wide quarantine view
        # (sampled rounds only refresh the sampled ids' standing)
        self._cohort_quarantine: set | None = None
        self._active_execution_mode = EXEC_PIPELINED
        self._consumer: RoundConsumer | None = None
        self._prefetcher: RoundPrefetcher | None = None
        # fit()'s open `fit_prologue` span (see _fit_loop / _end_prologue)
        self._prologue_span = None
        # cohort-slot ordering handle: the consumer sets this event once it
        # has scattered round r's rows into the registry, and the producer
        # waits on it before gathering round r+1's state (read-after-write
        # through the host registry; data staging is NOT gated on it)
        self._registry_scatter_event = None
        self._ckpt_writer: AsyncCheckpointWriter | None = None
        self._fit_n_rounds = 0
        # facts of the restore a fit() performed (manifest `resume`
        # descriptor); None on fresh runs
        self._resume_info: dict | None = None
        # per-event prefix digests of the async plan (computed when async
        # checkpointing is active; event e's snapshot stores entry e-1)
        self._async_prefix_fps: list[str] | None = None
        # Measured per-round program FLOPs from build-time introspection
        # (observability/introspect.py); None until a fit() captures it.
        # Feeds the measured-MFU numbers in _record_round_metrics.
        self._round_program_flops: float | None = None
        # per-client scheduled local-step counts (from the fixed round
        # plan), computed lazily for the per-chip steps/s round metric
        self._steps_per_client_cache: np.ndarray | None = None
        self.rng = jax.random.PRNGKey(seed)
        self._device_kind = getattr(jax.devices()[0], "device_kind", None)
        if self._cohort_active:
            # slot programs take sample_counts as a TRACED input (the PR 11
            # hook) — the cohort's true counts are staged per round; this
            # baked placeholder is never dispatched
            self.sample_counts = jnp.zeros((self.n_clients,), jnp.float32)
        else:
            self.sample_counts = jnp.asarray(
                [d.n_train for d in self.datasets], jnp.float32
            )
        self.history: list[RoundRecord] = []

        # x/y row counts must agree within each client and split: n_train is
        # derived from x, so a short y would silently pair tail examples with
        # zero-padded labels after stacking.
        for i, d in enumerate(self.datasets):
            if d.y_test is not None and d.x_test is None:
                # mirror of the x-without-y case below: silently ignoring the
                # labels would skip a test evaluation the user asked for
                raise ValueError(f"client {i}: y_test set but x_test is None")
        have_test = [d.x_test is not None for d in self.datasets]
        if any(have_test) and not all(have_test):
            missing = [i for i, h in enumerate(have_test) if not h]
            raise ValueError(
                f"clients {missing} have no test split while others do; "
                "provide x_test/y_test for every client or none."
            )
        self._has_test_split = all(have_test) and len(have_test) > 0
        for i, d in enumerate(self.datasets):
            splits = [(d.x_train, d.y_train, "train"), (d.x_val, d.y_val, "val")]
            if self._has_test_split:
                if d.y_test is None:
                    raise ValueError(f"client {i}: x_test set but y_test is None")
                splits.append((d.x_test, d.y_test, "test"))
            for xs, ys, split in splits:
                nx, ny = engine.data_rows(xs), engine.data_rows(ys)
                if nx != ny:
                    raise ValueError(
                        f"client {i}: x_{split} has {nx} rows but y_{split} "
                        f"has {ny}; each client's features and labels must "
                        "pair one-to-one."
                    )

        # Pre-stacked per-client data (one-time, device-resident) feeding the
        # per-round single-gather batch construction (engine.gather_batches).
        # The banks deliberately stay UNSHARDED here: the pipelined
        # prefetcher's worker thread gathers batches from them eagerly, and
        # an eager multi-device gather racing the main thread's round
        # dispatch deadlocks (two threads enqueueing multi-device launches
        # in different per-device orders). The chunked dispatches — the only
        # programs that take the banks as jit inputs — stage a sharded copy
        # once via _sharded_train_banks() instead.
        if self._cohort_active:
            # no O(N) device banks in cohort mode: per-round slot batches
            # are assembled host-side from the registry and staged through
            # the prefetcher (data never exceeds O(slots) on device)
            self._x_train_stack = self._y_train_stack = None
            self._x_val_stack = self._y_val_stack = None
            self._sharded_banks_cache: tuple | None = None
        else:
            self._x_train_stack = engine.pad_and_stack_data([d.x_train for d in self.datasets], "x_train")
            self._y_train_stack = engine.pad_and_stack_data([d.y_train for d in self.datasets], "y_train")
            self._sharded_banks_cache = None
            self._x_val_stack = engine.pad_and_stack_data([d.x_val for d in self.datasets], "x_val")
            self._y_val_stack = engine.pad_and_stack_data([d.y_val for d in self.datasets], "y_val")
        self._base_entropy = engine._entropy_from_key(self.rng)
        self._val_cache: tuple[Batch, jax.Array] | None = None
        self._test_cache: tuple[Batch, jax.Array] | None = None

        # --- init client + server state -----------------------------------
        self._init_states(_wire_zero1=True)

        self._build_compiled()
        if self._per_client is not None:
            self._report_parameter_split()

    # ------------------------------------------------------------------
    def _init_states(self, _wire_zero1: bool = False) -> None:
        """(Re)initialize the client-stacked ``TrainState`` and the server
        state from ``self.rng`` — exactly the constructor's derivation,
        factored out so the sweep engine (``fl4health_tpu/sweep/``) can
        re-seed a template simulation per grid cell without rebuilding its
        closures/compiled programs::

            sim.rng = jax.random.PRNGKey(seed)
            sim._base_entropy = engine._entropy_from_key(sim.rng)
            sim._init_states()

        reproduces bit-identically the states a fresh construction with
        that seed would build. ``_wire_zero1`` runs the one-time ZeRO-1
        server-optimizer wiring and is only passed by ``__init__``."""
        init_rng = jax.random.fold_in(self.rng, 0)
        if self._cohort_active:
            sample_x = jax.tree_util.tree_map(
                jnp.asarray, self.registry.sample_x()
            )
        else:
            sample_x = jax.tree_util.tree_map(
                lambda a: a[:1], self.datasets[0].x_train
            )
        split_init = shared_abstract = None
        if self._per_client is not None:
            # the shared leaves stay abstract until the first fit() or
            # set_global_params: a pretrained base is installed over them,
            # and two copies of it need not fit the device
            *split_init, shared_abstract, self._make_shared = (
                engine.init_split(self.logic.model, init_rng, sample_x)
            )
        proto = engine.create_train_state(
            self.logic, self.tx, init_rng, sample_x, precision=self.precision,
            init=split_init,
        )
        if (_wire_zero1 and self._program_builder.mesh is not None
                and self.mesh_config.zero1):
            # ZeRO-1 server optimizer (parallel/zero.py) over the SAME mesh
            # the round programs dispatch on — each replica owns 1/N of the
            # server momenta; the construction-time parity probe therefore
            # validates the deployed sharding, not a throwaway mesh.
            self._wire_zero1_server_optimizer(proto.params)
        per_client = []
        for i in range(self.n_clients):
            # All clients share the server's initial params (the reference's
            # round-1 initialize_all_model_weights broadcast covers the FULL
            # model, basic_client.py:205 — including personal subtrees that
            # never cross the wire afterwards); only the PRNG stream differs.
            st = proto.replace(rng=jax.random.fold_in(init_rng, i + 1))
            per_client.append(st)
        self.client_states: TrainState = ptu.stack_clients(per_client)
        # self.strategy, not a local: zero1 wiring may have rebuilt the
        # chain around a ZeRO-sharded server optimizer
        if shared_abstract is not None:
            self.server_state = self.strategy.init_split(
                proto.params, shared_abstract
            )
        else:
            self.server_state = self.strategy.init(proto.params)
        if self._cohort_active:
            # bind the registry's prototype rows: client i's TrainState row
            # derives from (proto, fold_in(init_rng, i+1)) — the dense
            # constructor's exact per-client derivation — and the
            # strategy's per-client server rows from the slot init's row 0
            # (client-symmetric start, verified by bind_strategy_rows)
            self.registry.bind_client_states(proto, init_rng)
            self.registry.bind_strategy_rows(
                self.strategy.state_rows(self.server_state)
            )

    # ------------------------------------------------------------------
    def set_train_data(self, xs: Sequence[Any], ys: Sequence[Any]) -> None:
        """Swap every client's training arrays in place — the host half of
        per-round data refresh (e.g. fresh nnU-Net patch banks). Shapes and
        dtypes must match the originals: the compiled round program is traced
        against the stacked layout and must not be invalidated."""
        if self._cohort_active:
            raise ValueError(
                "set_train_data swaps the dense device banks; a cohort-slot "
                "simulation has none — refresh the registry's data source "
                "instead (the next round's staging reads it)"
            )
        def coerce(d):
            # Preserve pre-pytree behavior for array-likes (lists of rows
            # coerce to ONE array); only Mapping inputs are treated as
            # multi-input pytrees.
            from collections.abc import Mapping

            if isinstance(d, Mapping):
                return jax.tree_util.tree_map(jnp.asarray, d)
            return jnp.asarray(d)

        new_x = engine.pad_and_stack_data([coerce(x) for x in xs], "x_train")
        new_y = engine.pad_and_stack_data([coerce(y) for y in ys], "y_train")
        for name, new, old in (("x_train", new_x, self._x_train_stack),
                               ("y_train", new_y, self._y_train_stack)):
            if (jax.tree_util.tree_structure(new)
                    != jax.tree_util.tree_structure(old)):
                raise ValueError(
                    f"set_train_data: {name} pytree structure changed "
                    "(per-round refresh may not change the data layout)"
                )
            for (pa, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(new)[0],
                jax.tree_util.tree_flatten_with_path(old)[0],
            ):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        f"set_train_data: {name}{engine.path_str(pa)} stack "
                        f"{a.shape}/{a.dtype} must match the original "
                        f"{b.shape}/{b.dtype} (per-round refresh may not "
                        "change the data layout)"
                    )
        self._x_train_stack = new_x
        self._y_train_stack = new_y
        # the swapped banks invalidate any staged sharded copy (identity
        # check in _sharded_train_banks)
        self._sharded_banks_cache = None

    # ------------------------------------------------------------------
    def _wire_zero1_server_optimizer(self, params_template) -> None:
        """Wire ``parallel/zero.py`` into the server optimizer
        (``MeshConfig(zero1=True)``): the innermost strategy must be
        FedOpt-family (it OWNS a server optax transform); its ``tx`` is
        wrapped so the flat server-momenta vector is partitioned over the
        clients (replica) axis — Xu et al.'s cross-replica sharding of the
        weight update. The one-step sharded-vs-unsharded parity probe runs
        against THIS mesh (the one ``fit()`` dispatches on).

        The caller's strategy object is never mutated: the wrapper chain is
        rebuilt around shallow copies and ``self.strategy`` reassigned, so a
        strategy instance reused by another simulation (the natural
        sharded-vs-unsharded comparison) keeps its plain ``tx``."""
        import copy

        from fl4health_tpu.parallel.zero import (
            Zero2ShardedOptimizer,
            ZeroShardedOptimizer,
            _validate_elementwise,
            zero_sharded_optimizer,
        )
        from fl4health_tpu.strategies.fedopt import FedOpt

        chain = [self.strategy]
        while hasattr(chain[-1], "inner"):
            chain.append(chain[-1].inner)
        inner = chain[-1]
        if not isinstance(inner, FedOpt):
            raise ValueError(
                "MeshConfig(zero1=True) shards a SERVER optimizer: the "
                "(innermost) strategy must be FedOpt-family (fed_adam/"
                "fed_yogi/fed_adagrad/fed_avg_m/FedOpt); got "
                f"{type(inner).__name__}, which has no server optax "
                "transform to shard"
            )
        mesh = self._program_builder.mesh
        if isinstance(inner.tx, (ZeroShardedOptimizer, Zero2ShardedOptimizer)):
            # Already sharded by the caller: the probe must still reflect
            # the DEPLOYED mesh — a wrapper validated on a different mesh
            # certifies nothing about this run's sharding.
            if inner.tx.mesh != mesh or inner.tx.axis_name != CLIENTS_AXIS:
                raise ValueError(
                    "the server optimizer was ZeRO-sharded against a "
                    f"different mesh/axis ({inner.tx.axis_name!r} on "
                    f"{dict(inner.tx.mesh.shape)}) than the round programs "
                    f"dispatch on ({CLIENTS_AXIS!r} on {dict(mesh.shape)}); "
                    "let MeshConfig(zero1=True) do the wiring (pass the "
                    "plain optax transform) so validation reflects the "
                    "deployed sharding"
                )
            if self.mesh_config.validate_zero1:
                n_local = (inner.tx.n_shards
                           if isinstance(inner.tx, Zero2ShardedOptimizer)
                           else None)
                _validate_elementwise(
                    inner.tx, inner.tx.tx, params_template, n_local=n_local
                )
            return
        new_inner = copy.copy(inner)
        new_inner.tx = zero_sharded_optimizer(
            inner.tx, mesh, params_template, axis_name=CLIENTS_AXIS,
            validate=self.mesh_config.validate_zero1,
        )
        rebuilt = new_inner
        for wrapper in reversed(chain[:-1]):
            wrapper = copy.copy(wrapper)
            wrapper.inner = rebuilt
            rebuilt = wrapper
        self.strategy = rebuilt

    # ------------------------------------------------------------------
    def _build_compiled(self):
        # In-graph telemetry (observability/telemetry.py) is a compile-time
        # property of the round programs: the plain 5-output fit_round /
        # eval_round keep their signature for every external caller
        # (servers.py warm starts, bench, direct-test drivers, fit_chunk),
        # and telemetry-enabled fit() dispatches the *_t variants whose
        # extra output is the RoundTelemetry pytree.
        self._telemetry_enabled = self.observability.telemetry_enabled
        self._fit_round_fn, self._eval_round_fn = self._build_round_fns(False)
        # Every compiled round program is constructed by the
        # RoundProgramBuilder (parallel/program.py) — placement policy in
        # one place. mesh=None: b.jit IS jax.jit(fn, donate_argnums=...),
        # the pre-mesh program. With a mesh, the [C, ...] inputs/outputs
        # get NamedSharding(P("clients")) and the server state replicates
        # (or ZeRO-1-shards) via in_shardings/out_shardings.
        b = self._program_builder
        cs = b.client_sharding()
        rep = b.replicated()
        if b.mesh is not None:
            sh_clients = b.client_state_shardings(self.client_states)
            sh_server = b.server_state_shardings(
                self.strategy, self.server_state
            )
            # fit_round(server_state, client_states, batches, mask,
            #           round_idx, val_batches)
            self._fit_in_sh = (sh_server, sh_clients, cs, cs, rep, cs)
            if self._cohort_active:
                # cohort dispatches pass the per-round sample_counts as a
                # 7th (traced) argument — a [K] per-slot vector, clients
                # axis like the mask
                self._fit_in_sh = self._fit_in_sh + (cs,)
            self._fit_out_sh = (sh_server, sh_clients, None, None, None)
            # eval_round(server_state, client_states, batches, eval_counts)
            self._eval_in_sh = (sh_server, sh_clients, cs, cs)
            self._eval_out_sh = (sh_clients, None, None, None, None)
        else:
            sh_clients = sh_server = None
            self._fit_in_sh = self._fit_out_sh = None
            self._eval_in_sh = self._eval_out_sh = None
        self._sh_client_states = sh_clients
        self._sh_server_state = sh_server
        # Donation (mirroring fit_chunk's donate_argnums=(0,1), per
        # arXiv:2004.13336's reuse-the-replica-buffers rule): the full
        # client-weight stack and server state are updated IN PLACE each
        # round instead of copied — halves the steady-state footprint of the
        # big-cohort configs and removes an alloc+copy from the hot path.
        # CONTRACT for every caller: treat the passed-in states as INVALID
        # after the call — always replace them with the returned ones.
        # (Donation is gated off the CPU backend — see _donate_argnums —
        # but call sites must stay donation-safe for the TPU path; the
        # sharded builds route through the SAME gating.) eval donates only
        # the client stack: its server_state flows on to
        # update_after_eval/test-eval on the caller side.
        self._fit_round = b.jit(
            self._fit_round_fn, donate=(0, 1),
            in_shardings=self._fit_in_sh, out_shardings=self._fit_out_sh,
        )
        self._eval_round = b.jit(
            self._eval_round_fn, donate=(1,),
            in_shardings=self._eval_in_sh, out_shardings=self._eval_out_sh,
        )
        self._fit_round_fn_t = self._eval_round_fn_t = None
        self._fit_round_t = self._eval_round_t = None
        if self._telemetry_enabled:
            self._fit_round_fn_t, self._eval_round_fn_t = (
                self._build_round_fns(True)
            )
            # telemetry variants append ONE output (RoundTelemetry / the
            # per-client non-finite eval count) — unconstrained placement
            fit_out_t = (self._fit_out_sh + (None,)
                         if self._fit_out_sh is not None else None)
            eval_out_t = (self._eval_out_sh + (None,)
                          if self._eval_out_sh is not None else None)
            self._fit_round_t = b.jit(
                self._fit_round_fn_t, donate=(0, 1),
                in_shardings=self._fit_in_sh, out_shardings=fit_out_t,
            )
            self._eval_round_t = b.jit(
                self._eval_round_fn_t, donate=(1,),
                in_shardings=self._eval_in_sh, out_shardings=eval_out_t,
            )
        self._chunked_fit = None  # compiled lazily by make_chunked_fit
        self._chunked_fit_eval = None  # compiled lazily (fit()'s chunked route)
        # cohort chunked-scan program (in-graph draw + window exchange),
        # compiled lazily by _make_cohort_chunk — cohort runs only
        self._cohort_chunk_jit = None
        # Buffered-async programs (compiled lazily by _make_async_programs /
        # _make_async_chunked — only ever built when async_config is set,
        # so a synchronous simulation compiles exactly the pre-async set)
        self._async_prologue_jit = None
        self._async_event_jit = None
        self._async_chunked_jit = None
        self._async_plan = None  # the run's static event plan (host numpy)
        self._async_pending = None  # in-flight update buffer (device tree)

    def _build_client_fns(self, collect_telemetry: bool, logic=None):
        """Build the client-level (client_fit, client_eval) closures —
        pull -> local train -> push, and pull -> eval. ONE definition
        shared by the synchronous round programs (:meth:`_build_round_fns`)
        and the buffered-async event programs (:meth:`_build_async_fns`),
        so async and sync rounds run bit-identical client math by
        construction. ``logic`` replaces ``self.logic`` (the round programs
        of a model with shared leaves pass it bound to them)."""
        logic = logic if logic is not None else self.logic
        tx, strategy, exchanger = self.tx, self.strategy, self.exchanger
        loss_keys = ("backward", *self._extra_keys())
        if collect_telemetry:
            # logic-declared telemetry channels (e.g. the DP clip fraction)
            # enter the loss meter only on the telemetry build — the plain
            # programs stay exactly as before
            loss_keys += tuple(
                k for k in getattr(logic, "telemetry_loss_keys", ())
                if k not in loss_keys
            )
        if self.early_stopping is not None:
            es_train = engine.make_local_train_with_early_stopping(
                logic, tx, self.metrics, self.early_stopping, loss_keys,
                collect_telemetry=collect_telemetry,
                precision=self.precision,
            )
            train = None
        elif self.flash_early_stopping is not None:
            from fl4health_tpu.clients.flash import make_flash_local_train

            # flash's gamma-rule train has no telemetry accumulator: engine
            # stats come back NaN (update_norm/divergence/nonfinite still
            # measure — they are computed outside the train scan)
            es_train = make_flash_local_train(
                logic, tx, self.metrics, self.flash_early_stopping, loss_keys,
                precision=self.precision,
            )
            train = None
        else:
            es_train = None
            train = engine.make_local_train(
                logic, tx, self.metrics, loss_keys,
                collect_telemetry=collect_telemetry,
                precision=self.precision,
            )
        evaluate = engine.make_local_eval(logic, self.metrics, ("checkpoint", *self._eval_keys()))

        evaluate_after_fit = getattr(strategy, "evaluate_after_fit", False)

        wants_packet = getattr(exchanger, "wants_packet_payload", False)
        scaling_active = self._precision_scaling

        def client_fit(state: TrainState, payload, batches: Batch, participate,
                       val_batches: Batch):
            orig = state
            payload_params = payload.params if hasattr(payload, "params") else payload
            pull_src = payload if wants_packet else payload_params
            pulled = exchanger.pull(pull_src, state.params)
            state = state.replace(params=pulled)
            ctx = logic.init_round_context(state, payload)
            if es_train is not None:
                outs = es_train(state, ctx, batches, val_batches)
            else:
                outs = train(state, ctx, batches)
            if len(outs) == 5:
                new_state, losses, metrics, n_steps, engine_telem = outs
            else:
                new_state, losses, metrics, n_steps = outs
                engine_telem = (
                    telem.nan_engine_telemetry() if collect_telemetry else None
                )
            if evaluate_after_fit:
                # pre-aggregation local validation (FedDG-GA's
                # evaluate_after_fit=True requirement, feddg_ga.py:205-210)
                post_fit_losses, _ = evaluate(new_state, ctx, val_batches)
                losses = {**losses, "val_checkpoint_post_fit": post_fit_losses["checkpoint"]}
            client_telem = None
            if collect_telemetry:
                # update norm against the pulled globals, on the TRAINED
                # state (pre participation-masking: a non-participant's row
                # is garbage-by-construction and the watchdog filters by
                # mask, exactly like the loss rows)
                client_telem = {
                    **engine_telem,
                    "update_norm": telem.global_norm_diff(
                        new_state.params, pulled
                    ),
                }
            # non-participants neither pull nor train (their packet row is
            # garbage but aggregation hard-zeroes masked rows)
            new_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(participate > 0, n, o), new_state, orig
            )
            if collect_telemetry and scaling_active:
                # cumulative skipped-optimizer-step count from the carried
                # scaler state, AFTER participation masking (a
                # non-participant reports its carried value, not garbage)
                client_telem["loss_scale_skips"] = new_state.loss_scale[
                    "skipped"
                ]
            pushed = exchanger.push(new_state.params, pulled)
            packet = logic.pack(new_state, pushed, losses)
            if collect_telemetry:
                return new_state, packet, losses, metrics, client_telem
            return new_state, packet, losses, metrics

        def client_eval(state: TrainState, payload, batches: Batch):
            payload_params = payload.params if hasattr(payload, "params") else payload
            pull_src = payload if wants_packet else payload_params
            pulled = exchanger.pull(pull_src, state.params)
            st = state.replace(params=pulled)
            ctx = logic.init_round_context(st, payload)
            losses, metrics = evaluate(st, ctx, batches)
            return st, losses, metrics

        return client_fit, client_eval

    def _build_round_fns(self, collect_telemetry: bool):
        """Build (fit_round, eval_round) closures. With ``collect_telemetry``
        each appends one extra output — fit_round a :class:`RoundTelemetry`
        pytree, eval_round the per-client non-finite eval-loss count — all
        derived from values the program already computes, so the training
        math (and thus the loss trajectory) is bit-identical either way.

        ``fit_round`` carries one OPTIONAL trailing ``sample_counts``
        parameter: every historical caller omits it (the closure bakes
        ``self.sample_counts`` exactly as before), while the sweep engine's
        cell programs (``fl4health_tpu/sweep/``) pass it as a TRACED input
        so cells whose data partitions (and thus per-client train-set
        sizes) differ still share one compiled program."""
        base_client_fit, base_client_eval = self._build_client_fns(
            collect_telemetry)
        strategy = self.strategy
        shared_fns = None
        if self._per_client is not None:
            def shared_fns(server_state):
                """(client_fit, client_eval) over this round's shared
                leaves: read from the server state and bound to the model's
                forward once, here, outside the client vmap and the
                local-step scan."""
                return self._build_client_fns(
                    collect_telemetry, engine.bind_shared(
                        self.logic, strategy.shared_params(server_state)))
        baked_sample_counts = self.sample_counts
        spmd_axis = self._program_builder.spmd_axis_name

        # Chaos layer (resilience/faults.py): compiled into the round
        # program so the same seeded plan injects identical faults on both
        # execution modes. With no plan (or an empty one) neither branch
        # traces — the closure is exactly the pre-resilience program.
        fault_plan = self._fault_plan
        inject_dropout = (fault_plan is not None
                          and bool(getattr(fault_plan, "dropout_faults", ())))
        inject_corruption = (
            fault_plan is not None
            and bool(getattr(fault_plan, "corruption_faults", ()))
        )
        n_clients = self.n_clients

        def fit_round(server_state, client_states, batches, mask, round_idx,
                      val_batches, sample_counts=None):
            if sample_counts is None:
                sample_counts = baked_sample_counts
            payload = strategy.client_payload(server_state, round_idx)
            client_fit = (base_client_fit if shared_fns is None
                          else shared_fns(server_state)[0])
            if inject_dropout:
                # a dropped client is exactly an unsampled one: mask math,
                # never a shape change
                mask = mask * fault_plan.participation_factor(
                    round_idx, n_clients
                )
            vmapped = jax.vmap(client_fit, in_axes=(0, None, 0, 0, 0),
                               spmd_axis_name=spmd_axis)(
                client_states, payload, batches, mask, val_batches
            )
            if collect_telemetry:
                new_states, packets, losses, metrics, client_telem = vmapped
            else:
                new_states, packets, losses, metrics = vmapped
            if inject_corruption:
                # corrupt the WIRE update (what aggregation consumes), not
                # the client's local state — byzantine clients train
                # honestly and lie upstream, the standard attack model
                payload_params = (payload.params if hasattr(payload, "params")
                                  else payload)
                packets = fault_plan.corrupt_packets(
                    packets, payload_params, round_idx, n_clients
                )
            # Failed clients (non-finite loss) are excluded from aggregation,
            # matching the reference where failures never enter results
            # (strategies/basic_fedavg.py:254-256 skips on failures; here the
            # per-client row is masked out so the aggregate stays clean).
            finite = jnp.isfinite(losses.get("backward", jnp.zeros_like(mask)))
            agg_mask = mask * finite.astype(mask.dtype)
            results = FitResults(
                packets=packets,
                sample_counts=sample_counts,
                train_losses=losses,
                train_metrics=metrics,
                mask=agg_mask,
            )
            with stage_attr.stage("server_update"):
                new_server_state = strategy.aggregate(
                    server_state, results, round_idx
                )
            w = results.mask * sample_counts
            agg_losses = {
                # where() not multiply: an excluded client's NaN loss must not
                # poison the weighted mean (NaN * 0 == NaN).
                k: jnp.sum(jnp.where(results.mask > 0, v, 0.0) * w)
                / jnp.maximum(jnp.sum(w), 1.0)
                for k, v in losses.items()
            }
            agg_metrics = aggregate_metrics(metrics, sample_counts, results.mask)
            if not collect_telemetry:
                return new_server_state, new_states, agg_losses, agg_metrics, losses
            nan_row = jnp.full_like(
                jnp.asarray(losses["backward"], jnp.float32), jnp.nan
            )
            round_telemetry = RoundTelemetry(
                train_loss=jnp.asarray(losses["backward"], jnp.float32),
                train_loss_min=client_telem["train_loss_min"],
                train_loss_max=client_telem["train_loss_max"],
                grad_norm_mean=client_telem["grad_norm_mean"],
                grad_norm_max=client_telem["grad_norm_max"],
                update_norm=client_telem["update_norm"],
                clip_fraction=losses.get("clip_fraction", nan_row),
                nonfinite_params=telem.per_client_nonfinite(new_states.params),
                nonfinite_loss=telem.nonfinite_in_losses(losses),
                divergence=telem.per_client_divergence(
                    new_states.params,
                    strategy.divergence_reference(new_server_state),
                ),
                nonfinite_eval_loss=jnp.zeros_like(nan_row),
                # fp16 scaler visibility: cumulative skipped-step count per
                # client; None (an empty pytree node) without loss scaling,
                # so legacy telemetry records keep their exact shape
                loss_scale_skips=client_telem.get("loss_scale_skips"),
            )
            return (new_server_state, new_states, agg_losses, agg_metrics,
                    losses, round_telemetry)

        def eval_round(server_state, client_states, batches, eval_counts):
            gp = strategy.client_payload(server_state, jnp.zeros((), jnp.int32))
            client_eval = (base_client_eval if shared_fns is None
                           else shared_fns(server_state)[1])
            with stage_attr.stage("evaluate"):
                new_states, losses, metrics = jax.vmap(
                    client_eval, in_axes=(0, None, 0),
                    spmd_axis_name=spmd_axis
                )(client_states, gp, batches)
            agg_losses = {
                k: jnp.sum(v * eval_counts) / jnp.maximum(jnp.sum(eval_counts), 1.0)
                for k, v in losses.items()
            }
            agg_metrics = aggregate_metrics(metrics, eval_counts)
            if collect_telemetry:
                return (new_states, agg_losses, agg_metrics, losses, metrics,
                        telem.nonfinite_in_losses(losses))
            return new_states, agg_losses, agg_metrics, losses, metrics

        return fit_round, eval_round

    def _extra_keys(self):
        # explicit constructor keys win; else the logic's declared keys
        if self._extra_loss_keys:
            return self._extra_loss_keys
        return getattr(self.logic, "extra_loss_keys", ())

    def _eval_keys(self):
        if self._eval_loss_keys:
            return self._eval_loss_keys
        return getattr(self.logic, "eval_loss_keys", ())

    # ------------------------------------------------------------------
    def _round_plan(self, round_idx: int):
        """Host-side index plan (numpy idx/example_mask/step_mask) for one
        round — the same plan whether gathered per round (``fit``) or stacked
        for the on-device multi-round scan (``fit_chunk``)."""
        entropies = [
            [*self._base_entropy, 1000 + round_idx, i] for i in range(self.n_clients)
        ]
        return engine.multi_client_index_plans(
            entropies,
            [d.n_train for d in self.datasets],
            self.batch_size,
            n_steps=self.local_steps,
            local_epochs=self.local_epochs,
        )

    def _sharded_train_banks(self):
        """The [C, ...] train banks staged onto the clients axis, cached
        until ``set_train_data`` swaps them. The chunked programs take the
        banks as jit inputs with ``in_shardings`` pinned to P("clients"),
        so passing the unsharded construction-time banks would reshard the
        FULL per-client data bank — a cross-device copy of every client's
        whole dataset — on every chunk dispatch. Without a mesh this
        returns the banks untouched. (The banks themselves must stay
        unsharded for the pipelined prefetcher — see the construction-time
        comment.)"""
        sh = self._program_builder.client_sharding()
        if sh is None:
            return self._x_train_stack, self._y_train_stack
        cached = self._sharded_banks_cache
        if (cached is not None and cached[0] is self._x_train_stack
                and cached[1] is self._y_train_stack):
            return cached[2], cached[3]
        xs = self._program_builder.put(self._x_train_stack, sh)
        ys = self._program_builder.put(self._y_train_stack, sh)
        self._sharded_banks_cache = (
            self._x_train_stack, self._y_train_stack, xs, ys
        )
        return xs, ys

    def _round_batches(self, round_idx: int) -> Batch:
        idx, em, sm = self._round_plan(round_idx)
        return engine.gather_batches(
            self._x_train_stack, self._y_train_stack, idx, em, sm
        )

    # ------------------------------------------------------------------
    def make_chunked_fit(self):
        """Compile a multi-round scan: ONE dispatch executes k federated
        rounds entirely on device, gathering each round's batches inside the
        scan from the resident data stacks. Each round's math is exactly
        ``_fit_round``'s on the same host index plans and the same per-round
        participation masks, so the trajectory matches the per-round path
        bit-for-bit — including sampled partial participation
        (tests/server/test_chunked_fit.py).

        NOT a drop-in for ``fit`` beyond that: the per-round failure-policy
        check / checkpointing / reporting — host-sync work — do not run
        inside the scan. Participation DOES match ``fit``: per-round masks
        are drawn host-side with the same PRNG stream and scanned over.

        The returned callable DONATES its first two arguments (server_state,
        client_states): on TPU the passed-in buffers are invalidated — always
        replace them with the outputs, as ``fit_chunk`` does. (CPU ignores
        donation, so misuse is only visible on device backends.)

        This is the SURVEY §7 "keep entire rounds (or multi-round chunks)
        on-device" lever: each dispatch costs host work between rounds, and
        amortizing it across k rounds removes the per-round dispatch latency
        from the hot loop. Used by ``fit_chunk`` and the bench.
        """
        if self._chunked_fit is not None:
            return self._chunked_fit
        fit_round = self._fit_round_fn

        def chunk(server_state, client_states, x_stack, y_stack, idx, em, sm,
                  masks, start_round, val_batches):
            def body(carry, per_round):
                server_state, client_states, r = carry
                idx_r, em_r, sm_r, mask_r = per_round
                batches = engine.gather_batches(x_stack, y_stack, idx_r, em_r, sm_r)
                server_state, client_states, losses, metrics, _ = fit_round(
                    server_state, client_states, batches, mask_r, r, val_batches
                )
                return (server_state, client_states, r + 1), (losses, metrics)

            (server_state, client_states, _), (losses, metrics) = jax.lax.scan(
                body, (server_state, client_states, start_round),
                (idx, em, sm, masks),
            )
            return server_state, client_states, losses, metrics

        # Donate the carried states: the caller always replaces them with the
        # scan's outputs, so XLA can update the (large, client-stacked)
        # buffers in place instead of allocating a second copy — on a 16GB
        # chip that halves the peak footprint of the big-cohort configs.
        # (No-op on CPU; data stacks are NOT donated.)
        b = self._program_builder
        in_sh = out_sh = None
        if b.mesh is not None:
            cs = b.client_sharding()
            scs = b.stacked_client_sharding()
            in_sh = (self._sh_server_state, self._sh_client_states, cs, cs,
                     scs, scs, scs, scs, b.replicated(), cs)
            out_sh = (self._sh_server_state, self._sh_client_states,
                      None, None)
        self._chunked_fit = b.jit(
            chunk, donate=(0, 1), in_shardings=in_sh, out_shardings=out_sh
        )
        return self._chunked_fit

    def fit_chunk(self, start_round: int, k: int, mask=None):
        """Run rounds [start_round, start_round+k) in one compiled dispatch.
        Returns per-round stacked (losses, metrics) dicts; updates the
        simulation state in place.

        Incompatible with ``train_data_provider``: the chunk bakes its data
        stacks at dispatch time, so per-round host refresh cannot happen
        inside it — raising beats silently training k rounds on a frozen
        bank.

        Participation matches ``fit``: each round's mask is drawn from the
        same PRNG stream (fold_in(rng, 2000+round)) via the client manager.
        Pass ``mask`` ([clients] or [k, clients]) to pin it instead."""
        self._ensure_shared()
        if self.train_data_provider is not None:
            raise ValueError(
                "fit_chunk cannot honor train_data_provider (per-round data "
                "refresh happens on the host, between dispatches); use "
                "fit(), or chunk with the provider disabled if a frozen "
                "bank is acceptable"
            )
        chunked = self.make_chunked_fit()
        plans = [self._round_plan(start_round + i) for i in range(k)]
        idx = jnp.asarray(np.stack([p[0] for p in plans]))
        em = jnp.asarray(np.stack([p[1] for p in plans]))
        sm = jnp.asarray(np.stack([p[2] for p in plans]))
        if mask is None:
            masks = jnp.stack([
                self.client_manager.sample(
                    jax.random.fold_in(self.rng, 2000 + start_round + i),
                    start_round + i,
                )
                for i in range(k)
            ])
        else:
            mask = jnp.asarray(mask)
            if mask.shape not in ((k, self.n_clients), (self.n_clients,)):
                raise ValueError(
                    f"fit_chunk mask must have shape ({k}, {self.n_clients}) "
                    f"or ({self.n_clients},); got {mask.shape}"
                )
            masks = mask if mask.ndim == 2 else jnp.broadcast_to(
                mask, (k,) + mask.shape
            )
        val_batches, _ = self._val_batches()
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        x_bank, y_bank = self._sharded_train_banks()
        self.server_state, self.client_states, losses, metrics = chunked(
            self.server_state, self.client_states,
            x_bank, y_bank, idx, em, sm, masks,
            jnp.asarray(start_round, jnp.int32), val_batches,
        )
        return losses, metrics

    def _make_chunked_fit_with_eval(self):
        """Compile fit()'s chunked route: a multi-round scan whose body runs
        the SAME fit_round + eval_round (+ optional test eval) sequence as
        one pipelined round — so a chunked fit() produces the same
        RoundRecord trajectory as the per-round path, in ONE dispatch for
        the whole run. Donates the carried states like make_chunked_fit.

        With telemetry enabled the scan body runs the telemetry round
        variants and stacks each round's :class:`RoundTelemetry` into the
        outputs — per-round training-health metrics ride the run's single
        fused device->host pull."""
        if self._chunked_fit_eval is not None:
            return self._chunked_fit_eval
        telemetry_on = self._telemetry_enabled
        fit_round = self._fit_round_fn_t if telemetry_on else self._fit_round_fn
        eval_round = self._eval_round_fn_t if telemetry_on else self._eval_round_fn
        quarantine_fn = (getattr(self.strategy, "quarantine_mask", None)
                         if self.observability.enabled else None)

        def chunk(server_state, client_states, x_stack, y_stack, idx, em, sm,
                  masks, start_round, val_batches, val_counts,
                  test_batches=None, test_counts=None):
            def body(carry, per_round):
                server_state, client_states, r = carry
                idx_r, em_r, sm_r, mask_r = per_round
                batches = engine.gather_batches(x_stack, y_stack, idx_r, em_r, sm_r)
                fit_outs = fit_round(
                    server_state, client_states, batches, mask_r, r,
                    val_batches,
                )
                round_telemetry = None
                if telemetry_on:
                    (server_state, client_states, fit_losses, fit_metrics,
                     per_fit, round_telemetry) = fit_outs
                else:
                    (server_state, client_states, fit_losses, fit_metrics,
                     per_fit) = fit_outs
                # mirror _run_round: post-aggregation eval refreshes the
                # client stack with the pulled global params
                ev_outs = eval_round(
                    server_state, client_states, val_batches, val_counts
                )
                if telemetry_on:
                    (client_states, ev_losses, ev_metrics, _pl, _pm,
                     ev_nonfinite) = ev_outs
                    round_telemetry = round_telemetry.replace(
                        nonfinite_eval_loss=ev_nonfinite
                    )
                else:
                    client_states, ev_losses, ev_metrics, _pl, _pm = ev_outs
                out = {
                    "fit_losses": fit_losses,
                    "fit_metrics": fit_metrics,
                    "per_client_fit_losses": per_fit,
                    "eval_losses": ev_losses,
                    "eval_metrics": ev_metrics,
                }
                if round_telemetry is not None:
                    out["telemetry"] = round_telemetry
                if quarantine_fn is not None:
                    # per-round in-graph quarantine mask stacks with the
                    # scan outputs — same fused pull, per-round visibility
                    out["quarantine"] = quarantine_fn(server_state)
                if test_batches is not None:
                    t_outs = eval_round(
                        server_state, client_states, test_batches, test_counts
                    )
                    out["test_losses"] = t_outs[1]
                    out["test_metrics"] = t_outs[2]
                return (server_state, client_states, r + 1), out

            (server_state, client_states, _), outs = jax.lax.scan(
                body, (server_state, client_states, start_round),
                (idx, em, sm, masks),
            )
            return server_state, client_states, outs

        b = self._program_builder
        in_sh = out_sh = None
        if b.mesh is not None:
            cs = b.client_sharding()
            scs = b.stacked_client_sharding()
            in_sh = (self._sh_server_state, self._sh_client_states, cs, cs,
                     scs, scs, scs, scs, b.replicated(), cs, cs)
            if self._test_batches() is not None:
                # arity must match the dispatch: test args ride along
                in_sh = in_sh + (cs, cs)
            out_sh = (self._sh_server_state, self._sh_client_states, None)
        self._chunked_fit_eval = b.jit(
            chunk, donate=(0, 1), in_shardings=in_sh, out_shardings=out_sh
        )
        return self._chunked_fit_eval

    # -- buffered-async programs (server/async_schedule.py) -------------
    def _build_async_fns(self, collect_telemetry: bool):
        """Build the (async_prologue, async_event) closures of the
        buffered-async mode (FedBuff-style, arXiv:2106.06639).

        One buffer-fill EVENT replaces one synchronous round:

            consume  — the K arrived updates (a row of the static event
                       plan) aggregate under the staleness-discounted
                       fractional mask (``FedBuff.async_aggregation_mask``);
            eval     — the post-aggregation global evaluates exactly like
                       a synchronous round's eval;
            restart  — the consumed clients pull the fresh global and run
                       their next local training, whose packet sits in the
                       carried ``pending`` buffer until a later event
                       consumes it.

        The prologue is event 0's missing half: every client trains from
        the initial global on data plan 1, filling ``pending``. Ordering
        (aggregate -> eval -> restart-on-post-eval-states) deliberately
        mirrors the synchronous round sequence, which is what makes the
        ``K = cohort, no stragglers`` plan bit-identical to sync fit() —
        same client math (shared ``_build_client_fns`` closures), same
        aggregation arithmetic, same round indices."""
        client_fit, _ = self._build_client_fns(collect_telemetry)
        spmd_axis = self._program_builder.spmd_axis_name
        _, eval_round = self._build_round_fns(collect_telemetry)
        strategy = self.strategy
        fault_plan = self._fault_plan
        inject_dropout = (fault_plan is not None
                          and bool(getattr(fault_plan, "dropout_faults", ())))
        inject_corruption = (
            fault_plan is not None
            and bool(getattr(fault_plan, "corruption_faults", ()))
        )
        n_clients = self.n_clients
        sample_counts = self.sample_counts
        # over the registry, a slot's sample count is a property of its
        # OCCUPANT — and aggregation consumes packets trained under a
        # possibly-evicted occupant, so the counts must ride the pending
        # buffer with the packet instead of being a closure constant
        cohort_active = self._cohort_active
        async_mask = getattr(strategy, "async_aggregation_mask", None)
        if async_mask is not None:
            import inspect

            # duck-typed hooks with the pre-hoisting 2-arg signature keep
            # working: only pass the traced exponent where it is accepted.
            # The exponent is passed POSITIONALLY, so only positional-
            # capable parameters count (**kwargs can never absorb it).
            _params = inspect.signature(async_mask).parameters.values()
            _positional = sum(
                1 for p in _params
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            )
            _takes_exponent = (_positional >= 3 or any(
                p.kind == p.VAR_POSITIONAL for p in _params
            ))
            if not _takes_exponent:
                raw_mask = async_mask
                async_mask = lambda arr, stal, _exp: raw_mask(arr, stal)  # noqa: E731
            elif not hasattr(strategy, "staleness_exponent"):
                # an exponent-taking hook on a strategy WITHOUT the
                # attribute would receive the 0.0 dispatch fallback —
                # (1+s)^0 = 1, silently no discounting; fail loudly
                raise ValueError(
                    f"{type(strategy).__name__}.async_aggregation_mask "
                    "accepts an exponent argument but the strategy exposes "
                    "no 'staleness_exponent' attribute for the async round "
                    "programs to feed it from; expose the attribute (as "
                    "FedBuff does), or drop the parameter to use internal "
                    "defaults"
                )
        quarantine_fn = (getattr(strategy, "quarantine_mask", None)
                         if self.observability.enabled else None)

        def train_wave(server_state, client_states, batches, train_mask,
                       round_idx, val_batches, wave_counts=None):
            """One training wave on data plan ``round_idx``: pull the
            current payload, locally train the masked clients, corrupt the
            wire packets with the SAME seeded round draws the sync path
            uses. Returns (new client stack, this wave's pending pieces).
            ``wave_counts`` (registry occupancy only) pins the per-slot
            sample counts the wave trained under into the pending buffer."""
            payload = strategy.client_payload(server_state, round_idx)
            vmapped = jax.vmap(client_fit, in_axes=(0, None, 0, 0, 0),
                               spmd_axis_name=spmd_axis)(
                client_states, payload, batches, train_mask, val_batches
            )
            if collect_telemetry:
                new_states, packets, losses, metrics, client_telem = vmapped
            else:
                new_states, packets, losses, metrics = vmapped
                client_telem = None
            if inject_corruption:
                payload_params = (payload.params
                                  if hasattr(payload, "params") else payload)
                packets = fault_plan.corrupt_packets(
                    packets, payload_params, round_idx, n_clients
                )
            pending = {"packets": packets, "losses": losses,
                       "metrics": metrics}
            if cohort_active:
                pending["sample_counts"] = (
                    sample_counts if wave_counts is None else wave_counts
                )
            if collect_telemetry:
                pending["telem"] = client_telem
            return new_states, pending

        def merge_pending(old, new, arrivals):
            """Per-leaf arrival-masked merge: an arrived client's slot
            takes its fresh wave output; everyone else's in-flight update
            stays buffered untouched."""
            def sel(n, o):
                a = arrivals.reshape((-1,) + (1,) * (n.ndim - 1))
                return jnp.where(a > 0, n, o)

            return jax.tree_util.tree_map(sel, new, old)

        def async_prologue(server_state, client_states, batches, val_batches,
                           wave_counts=None):
            ones = jnp.ones((n_clients,), jnp.float32)
            return train_wave(
                server_state, client_states, batches, ones,
                jnp.asarray(1, jnp.int32), val_batches, wave_counts,
            )

        def async_event(server_state, client_states, pending, batches_next,
                        arrivals, staleness, event_idx, val_batches,
                        val_counts, staleness_exponent,
                        test_batches=None, test_counts=None,
                        wave_counts=None):
            # -- consume: staleness-discounted aggregation of the buffer --
            # staleness_exponent is a TRACED scalar input (fed from the
            # live strategy attribute at each dispatch), so an exponent
            # sweep/rebind reuses this compiled program — the sweep
            # engine's scalar-hoisting contract
            arr = arrivals
            if inject_dropout:
                # a dropped update is lost on the wire: it fills its buffer
                # slot but aggregates with weight 0 (the client restarts
                # normally, keeping the static plan's bookkeeping exact)
                arr = arr * fault_plan.participation_factor(
                    event_idx, n_clients
                )
            disc_mask = (async_mask(arr, staleness, staleness_exponent)
                         if async_mask is not None else arr)
            finite = jnp.isfinite(
                pending["losses"].get("backward", jnp.zeros_like(arr))
            )
            agg_mask = disc_mask * finite.astype(disc_mask.dtype)
            # the counts the buffered packets TRAINED under (they rode the
            # pending buffer on the registry path — occupancy may have
            # changed since); the dense path's closure constant otherwise
            counts = (pending["sample_counts"] if cohort_active
                      else sample_counts)
            results = FitResults(
                packets=pending["packets"],
                sample_counts=counts,
                train_losses=pending["losses"],
                train_metrics=pending["metrics"],
                mask=agg_mask,
            )
            new_server = strategy.aggregate(server_state, results, event_idx)
            w = results.mask * counts
            agg_losses = {
                k: jnp.sum(jnp.where(results.mask > 0, v, 0.0) * w)
                / jnp.maximum(jnp.sum(w), 1.0)
                for k, v in pending["losses"].items()
            }
            agg_metrics = aggregate_metrics(
                pending["metrics"], counts, results.mask
            )
            round_telemetry = None
            if collect_telemetry:
                # telemetry describes the CONSUMED updates (like the loss
                # record): engine stats ride the pending buffer from train
                # time; divergence/nonfinite measure the live stack against
                # the fresh aggregate, exactly the sync definitions
                pt = pending["telem"]
                nan_row = jnp.full_like(
                    jnp.asarray(pending["losses"]["backward"], jnp.float32),
                    jnp.nan,
                )
                round_telemetry = RoundTelemetry(
                    train_loss=jnp.asarray(
                        pending["losses"]["backward"], jnp.float32
                    ),
                    train_loss_min=pt["train_loss_min"],
                    train_loss_max=pt["train_loss_max"],
                    grad_norm_mean=pt["grad_norm_mean"],
                    grad_norm_max=pt["grad_norm_max"],
                    update_norm=pt["update_norm"],
                    clip_fraction=pending["losses"].get(
                        "clip_fraction", nan_row
                    ),
                    nonfinite_params=telem.per_client_nonfinite(
                        client_states.params
                    ),
                    nonfinite_loss=telem.nonfinite_in_losses(
                        pending["losses"]
                    ),
                    divergence=telem.per_client_divergence(
                        client_states.params,
                        strategy.divergence_reference(new_server),
                    ),
                    nonfinite_eval_loss=jnp.zeros_like(nan_row),
                    loss_scale_skips=pt.get("loss_scale_skips"),
                )
            # -- eval: the fresh global, synchronous-round semantics ------
            ev_outs = eval_round(
                new_server, client_states, val_batches, val_counts
            )
            if collect_telemetry:
                (client_states, ev_losses, ev_metrics, _pl, _pm,
                 ev_nonfinite) = ev_outs
                round_telemetry = round_telemetry.replace(
                    nonfinite_eval_loss=ev_nonfinite
                )
            else:
                client_states, ev_losses, ev_metrics, _pl, _pm = ev_outs
            out = {
                "fit_losses": agg_losses,
                "fit_metrics": agg_metrics,
                "per_client_fit_losses": pending["losses"],
                "eval_losses": ev_losses,
                "eval_metrics": ev_metrics,
            }
            if round_telemetry is not None:
                out["telemetry"] = round_telemetry
            if quarantine_fn is not None:
                out["quarantine"] = quarantine_fn(new_server)
            if test_batches is not None:
                t_outs = eval_round(
                    new_server, client_states, test_batches, test_counts
                )
                client_states = t_outs[0]
                out["test_losses"] = t_outs[1]
                out["test_metrics"] = t_outs[2]
            # -- restart: consumed clients train for a later event --------
            # data plan event_idx+1 and the matching fault draws — the
            # index stream a synchronous round event_idx+1 would use
            client_states, fresh = train_wave(
                new_server, client_states, batches_next, arrivals,
                event_idx + 1, val_batches, wave_counts,
            )
            pending = merge_pending(pending, fresh, arrivals)
            return new_server, client_states, pending, out

        return async_prologue, async_event

    def _make_async_programs(self):
        """Jit the per-event async programs (the pipelined path). The
        prologue keeps its server-state input alive (event 1 consumes it);
        the event program donates all three carried trees."""
        if self._async_event_jit is not None:
            return self._async_prologue_jit, self._async_event_jit
        prologue, event = self._build_async_fns(self._telemetry_enabled)
        b = self._program_builder
        pro_in = ev_in = ev_out = None
        if b.mesh is not None:
            cs = b.client_sharding()
            rep = b.replicated()
            sh_c, sh_s = self._sh_client_states, self._sh_server_state
            pro_in = (sh_s, sh_c, cs, cs)
            ev_in = (sh_s, sh_c, cs, cs, cs, cs, rep, cs, cs, rep)
            if self._test_batches() is not None:
                ev_in = ev_in + (cs, cs)
            ev_out = (sh_s, sh_c, cs, None)
        self._async_prologue_jit = b.jit(
            prologue, donate=(1,),
            in_shardings=pro_in,
            out_shardings=(self._sh_client_states, b.client_sharding())
            if b.mesh is not None else None,
        )
        self._async_event_jit = b.jit(
            event, donate=(0, 1, 2), in_shardings=ev_in, out_shardings=ev_out,
        )
        return self._async_prologue_jit, self._async_event_jit

    def _make_async_chunked(self):
        """Compile the async chunked route: ONE lax.scan dispatch walks the
        whole static event plan — per-event arrivals/staleness rows and
        data plans scan over the carried (server, clients, pending) trees,
        so a buffered-async run costs two dispatches total (prologue +
        scan) exactly like the synchronous chunked path costs one."""
        if self._async_chunked_jit is not None:
            return self._async_chunked_jit
        _, event = self._build_async_fns(self._telemetry_enabled)

        def chunk(server_state, client_states, pending, x_stack, y_stack,
                  idx, em, sm, arrivals, staleness, start_event,
                  val_batches, val_counts, staleness_exponent,
                  test_batches=None, test_counts=None):
            def body(carry, per_event):
                server_state, client_states, pending, e = carry
                idx_r, em_r, sm_r, arr_r, stal_r = per_event
                batches_next = engine.gather_batches(
                    x_stack, y_stack, idx_r, em_r, sm_r
                )
                server_state, client_states, pending, out = event(
                    server_state, client_states, pending, batches_next,
                    arr_r, stal_r, e, val_batches, val_counts,
                    staleness_exponent, test_batches, test_counts,
                )
                return (server_state, client_states, pending, e + 1), out

            (server_state, client_states, pending, _e), outs = jax.lax.scan(
                body,
                (server_state, client_states, pending, start_event),
                (idx, em, sm, arrivals, staleness),
            )
            # pending is RETURNED: the next chunk (checkpoint boundary)
            # carries it forward, and the boundary snapshot persists it
            return server_state, client_states, pending, outs

        b = self._program_builder
        in_sh = out_sh = None
        if b.mesh is not None:
            cs = b.client_sharding()
            scs = b.stacked_client_sharding()
            in_sh = (self._sh_server_state, self._sh_client_states, cs,
                     cs, cs, scs, scs, scs, scs, scs, b.replicated(),
                     cs, cs, b.replicated())
            if self._test_batches() is not None:
                in_sh = in_sh + (cs, cs)
            out_sh = (self._sh_server_state, self._sh_client_states, cs,
                      None)
        self._async_chunked_jit = b.jit(
            chunk, donate=(0, 1, 2), in_shardings=in_sh, out_shardings=out_sh
        )
        return self._async_chunked_jit

    def _eval_split_batches(self, x_stack, y_stack, ns) -> tuple[Batch, jax.Array]:
        """Shared val/test eval batching: fixed-order full pass + counts —
        one implementation so both splits always score under the same rules."""
        idx, em, sm = engine.multi_client_index_plans(
            [[0]] * self.n_clients, ns, self.batch_size, shuffle=False
        )
        batches = engine.gather_batches(x_stack, y_stack, idx, em, sm)
        return batches, jnp.asarray(ns, jnp.float32)

    def _val_batches(self) -> tuple[Batch, jax.Array]:
        if self._val_cache is None:
            batches, counts = self._eval_split_batches(
                self._x_val_stack, self._y_val_stack,
                [engine.data_rows(d.x_val) for d in self.datasets],
            )
            # sharded staging (no-op without a mesh): the cache is reused
            # every round, so the clients-axis split is paid once here
            # instead of on each dispatch's implicit reshard
            batches = self._program_builder.put(
                batches, self._program_builder.client_sharding()
            )
            self._val_cache = (batches, counts)
        return self._val_cache

    def _test_batches(self) -> tuple[Batch, jax.Array] | None:
        """Separate test split (basic_client.py:867 test loader; metrics ride
        with eval under a "test - " prefix, base_server.py:545
        _unpack_metrics). Present only when EVERY client provides one
        (validated in __init__)."""
        if not self._has_test_split:
            return None
        if self._test_cache is None:
            x_stack = engine.pad_and_stack_data(
                [d.x_test for d in self.datasets], "x_test"
            )
            y_stack = engine.pad_and_stack_data(
                [d.y_test for d in self.datasets], "y_test"
            )
            batches, counts = self._eval_split_batches(
                x_stack, y_stack, [engine.data_rows(d.x_test) for d in self.datasets]
            )
            batches = self._program_builder.put(
                batches, self._program_builder.client_sharding()
            )
            self._test_cache = (batches, counts)
        return self._test_cache

    # ------------------------------------------------------------------
    def _chunk_ineligibility(self) -> str | None:
        """Why fit() may NOT route through the on-device chunked scan
        (None = eligible). Anything that needs the host between rounds
        forces the pipelined per-round path."""
        if self._cohort_active:
            # cohort-slot runs chunk too (the in-graph draw + window
            # exchange replace the per-round host gather/scatter) — only
            # the combinations that genuinely need the host between
            # sampled rounds still demote:
            if self._async_active:
                return ("buffered-async over the registry swaps slot "
                        "occupants host-side per event (pipelined "
                        "per-event path)")
            if getattr(self.client_manager, "draw_cohort", None) is None:
                return (f"{type(self.client_manager).__name__} provides no "
                        "in-graph draw_cohort; the cohort draw must run on "
                        "the host every round")
            if self.recovery_policy is not None:
                return ("recovery supervision refreshes the quarantine "
                        "keep-mask against the live registry every round")
            if self.mesh_config is not None:
                return ("mesh + cohort stages each round's slot tensors "
                        "with sharded per-round device_put; the chunk's "
                        "window exchange is unsharded")
        if self.train_data_provider is not None:
            return "train_data_provider needs a host data refresh every round"
        if self.model_checkpointers:
            return "per-round model checkpointing needs per-round host access"
        # Durable state checkpointing no longer demotes the chunked path:
        # snapshot-capable checkpointers save at chunk boundaries (the run
        # dispatches in checkpoint_every-round chunks and the snapshot
        # rides the existing boundary host touch). Only the legacy
        # sim-reading API — save_simulation(sim, round) against LIVE state
        # every round — still needs the per-round loop.
        if (self.state_checkpointer is not None
                and not hasattr(self.state_checkpointer,
                                "save_simulation_snapshot")):
            return ("legacy state checkpointer (save_simulation reads live "
                    "per-round state)")
        if not self.failure_policy.accept_failures:
            return "accept_failures=False must be able to terminate mid-run"
        # Observability per se no longer demotes the chunked path: in-graph
        # telemetry rides the scan outputs and the per-round gauges/JSONL
        # records are reconstructed from the stacked pull. Only the two
        # hooks that intrinsically need per-round dispatch still force the
        # pipelined path.
        if (self.observability.enabled
                and self.observability.profile_round_idx is not None
                and self.observability.output_dir is not None):
            # without an output_dir maybe_profile() is a guaranteed no-op —
            # demoting for it would cost the fast path and capture nothing
            return ("opt-in XProf capture (profile_round_idx) wraps one "
                    "round's dispatch")
        if self.observability.enabled and self.observability.per_round_spans:
            return ("per-round span fencing requested "
                    "(Observability(per_round_spans=True))")
        # wrapper strategies (e.g. resilience.QuarantiningStrategy) override
        # update_after_eval only to delegate — they declare whether the
        # WRAPPED strategy actually consumes per-round eval on the host
        overrides = getattr(self.strategy, "overrides_update_after_eval", None)
        if overrides is None:
            overrides = (type(self.strategy).update_after_eval
                         is not Strategy.update_after_eval)
        if overrides:
            return ("strategy overrides update_after_eval (host-side "
                    "per-round eval consumption)")
        return None

    def _select_execution_mode(self, n_rounds: int) -> tuple[str, str]:
        """(mode, reason) for this fit() call. 'auto' prefers the chunked
        scan (fastest: zero per-round host work) and falls back to the
        pipelined path with the blocking reason attached."""
        if n_rounds < 1:
            # graceful no-op for every mode (the pipelined loop simply runs
            # zero rounds) — fit(0) must not raise even when chunked is forced
            return EXEC_PIPELINED, "n_rounds < 1 (no rounds to run)"
        if self.execution_mode == "pipelined":
            return EXEC_PIPELINED, "forced by execution_mode='pipelined'"
        why = self._chunk_ineligibility()
        if self.execution_mode == "chunked":
            if why:
                raise ValueError(f"execution_mode='chunked' but {why}")
            return EXEC_CHUNKED, "forced by execution_mode='chunked'"
        if why:
            return EXEC_PIPELINED, why
        if self.observability.enabled and self.observability.admin is not None:
            # the admin plane retunes at per-round host boundaries; a
            # chunked dispatch has none. Only the AUTO path demotes —
            # forcing 'chunked' with an armed plane stays legal, and the
            # endpoint rejects submits with a structured mid_chunk error.
            return EXEC_PIPELINED, (
                "admin retune endpoint armed (live scalar rebinds apply "
                "at per-round boundaries)"
            )
        return EXEC_CHUNKED, "auto: no per-round host dependencies"

    def fit(self, n_rounds: int) -> list[RoundRecord]:
        if self.recovery_policy is not None:
            # self-healing mode: the RecoverySupervisor re-enters
            # _fit_unsupervised after each recoverable abnormal end
            # (rollback via the checkpoint ring, rung mitigation, resume)
            if self._recovery_supervisor is None:
                from fl4health_tpu.resilience.supervisor import (
                    RecoverySupervisor,
                )

                self._recovery_supervisor = RecoverySupervisor(
                    self, self.recovery_policy
                )
            return self._recovery_supervisor.run(n_rounds)
        return self._fit_unsupervised(n_rounds)

    def _fit_unsupervised(self, n_rounds: int) -> list[RoundRecord]:
        """One fit attempt with no recovery wrapper — the pre-supervisor
        ``fit()`` body (also the supervisor's per-attempt entry point)."""
        if self.profile_dir is not None:
            with jax.profiler.trace(self.profile_dir):
                return self._fit_loop(n_rounds)
        return self._fit_loop(n_rounds)

    def _reset_to_initial(self) -> None:
        """Roll the live training state back to the constructor's
        seed-derived init — the recovery supervisor's rollback when no
        durable checkpoint generation predates a failure. ``self.rng`` is
        never mutated by ``fit()`` (every draw is a pure ``fold_in``), so
        ``_init_states`` reproduces the fresh states bit-identically."""
        if self._cohort_active:
            self.registry.reset_rows()
        self._init_states()
        self.history = []
        self._async_pending = None
        # from-scratch rollback: lifetime records of the abandoned
        # trajectory's rounds must not survive into the replay (they
        # would double-count participation)
        ledger = self.observability.fleet_ledger
        if ledger is not None:
            ledger.clear()

    def _apply_recovery_keep(self, mask, rnd: int):
        """Multiply a round's sampling mask by the recovery supervisor's
        quarantine keep-mask. A pure pass-through (the exact input object)
        when no supervisor is attached or nothing is quarantined, so
        armed-but-never-engaged runs stay bit-identical."""
        sup = self._recovery_supervisor
        if sup is None:
            return mask
        keep = sup.keep_mask(rnd, self.n_clients)
        if keep is None:
            return mask
        return mask * jnp.asarray(keep, jnp.float32)

    def _apply_admin_retunes(self, rnd: int) -> None:
        """Round-boundary hook (producer thread, every pipelined path):
        drain the admin plane's pending/scheduled retunes and rebind them
        on the live run — state-kind scalars through the same
        ``apply_state_scalars`` the sweep uses (a server-state leaf swap:
        zero recompiles), live-attr scalars (async staleness exponent) via
        setattr picked up by the next dispatch. A no-op without an armed
        plane, so the default path stays bit-identical."""
        obs = self.observability
        admin = obs.admin if obs.enabled else None
        if admin is None:
            return
        values = admin.drain(rnd)
        if not values:
            return
        from fl4health_tpu.sweep import hoisting

        try:
            state_vals = {
                n: v for n, v in values.items()
                if hoisting.binding(n).kind == "state"
            }
            if state_vals:
                self.server_state = hoisting.apply_state_scalars(
                    self.strategy, self.server_state, state_vals
                )
            for name, value in values.items():
                if name not in state_vals:
                    b = hoisting.binding(name)
                    setattr(b.find(self.strategy), b.attr, float(value))
        except Exception:
            # submit() validated against this strategy chain, so this is a
            # race (e.g. strategy swapped between submit and drain) — a bad
            # retune must not kill a training run
            logging.getLogger(__name__).warning(
                "admin retune %r failed to apply at round %d",
                values, rnd, exc_info=True,
            )
            return
        admin.note_applied(rnd, values)
        obs.update_manifest({"admin": admin.descriptor()})

    def _note_recovery_round(self, rnd: int) -> None:
        """Round-epilogue hook (every execution path, after the watchdog
        passed): drives the supervisor's probation window and quarantine
        releases. No-op without a supervisor."""
        sup = self._recovery_supervisor
        if sup is not None:
            sup.note_round(rnd)

    def _end_prologue(self) -> None:
        """Close fit()'s ``fit_prologue`` span if it is still open."""
        span, self._prologue_span = self._prologue_span, None
        if span is not None:
            span.__exit__(None, None, None)

    def _fit_loop(self, n_rounds: int) -> list[RoundRecord]:
        obs = self.observability
        obs.start()  # re-arm after a previous fit()'s shutdown (idempotent)
        # Everything a fit() call does before its first round — mode
        # selection, resume, manifest, the `introspect` walk, the driver's
        # `setup` — is one span. It crosses into the per-round driver, whose
        # first _run_round closes it; every other driver's ends at the
        # hand-off below.
        self._prologue_span = obs.span("fit_prologue", cat="fit")
        self._prologue_span.__enter__()
        flight = obs.flight_recorder if obs.enabled else None
        if flight is not None:
            flight.clear()  # the black box records THIS run only
        fleet = obs.fleet_ledger if obs.enabled else None
        if fleet is not None:
            # fresh fit(): the ledger starts empty; _maybe_resume below
            # restores the checkpointed as-of state when resuming, so
            # re-run rounds absorb exactly once
            fleet.clear()
        self._last_epilogue_round = None  # per-run (RoundConsumer progress)
        self._ensure_shared()
        mode, mode_reason = self._select_execution_mode(n_rounds)
        self._active_execution_mode = mode
        self._round_program_flops = None  # re-measured per fit() (mode-shaped)
        self._last_quarantine = None  # transition accounting is per-run
        self._cohort_quarantine = None
        logging.getLogger(__name__).info(
            "fit: execution_mode=%s (%s)", mode, mode_reason
        )
        # Resume BEFORE the manifest/introspection: a restored run's
        # manifest carries its `resume` descriptor, and the chunked paths
        # size their dispatches from the remaining rounds. The async event
        # plan is derived first — the resume must fingerprint-verify the
        # consumed prefix against it.
        plan = None
        if self._async_active and n_rounds >= 1:
            from fl4health_tpu.server.async_schedule import (
                build_event_plan,
                build_registry_event_plan,
            )

            if self._cohort_active:
                # FedBuff over the registry: the slot-level schedule plus
                # the deterministic seating ledger (who occupies each slot
                # per restart wave)
                plan = build_registry_event_plan(
                    self.async_config, n_rounds, self.n_clients,
                    self.registry_size, self._fault_plan,
                )
            else:
                plan = build_event_plan(
                    self.async_config, n_rounds, self.n_clients,
                    self._fault_plan,
                )
            self._async_plan = plan
        try:
            start_round = self._maybe_resume(n_rounds, plan)
        except BaseException as resume_exc:
            # a failed restore (all generations corrupt, config mismatch)
            # still publishes its evidence and disarms the hooks this
            # fit() armed — a CheckpointCorruptError IS a postmortem
            self._end_prologue()
            self._dump_postmortem(resume_exc)
            obs.shutdown()
            raise
        if self._recovery_supervisor is not None:
            # post-restore hook: the supervisor re-applies its pending
            # mitigations (in-graph quarantine seeding, hoisted-scalar
            # overrides) onto the freshly restored state and keeps
            # /healthz at 503 while a recovery is mid-probation
            self._recovery_supervisor.on_resume(start_round)
        if obs.watchdog is not None and not self._telemetry_enabled:
            logging.getLogger(__name__).warning(
                "HealthWatchdog attached but in-graph telemetry is off "
                "(Observability(enabled=%s, telemetry=%s)) — no health "
                "checks will run.", obs.enabled, obs.telemetry,
            )
        if obs.enabled and obs.admin is not None:
            # arm the admin plane against THIS run: validation needs the
            # live strategy chain + execution mode (a chunked run rejects
            # submits with a structured mid_chunk error), and the manifest
            # must disclose the plane from round 0 for replayability
            obs.admin.bind_run(self.strategy, mode,
                               async_active=self._async_active)
            obs.update_manifest({"admin": obs.admin.descriptor()})
        if obs.enabled:
            obs.log_event("execution_mode", mode=mode, reason=mode_reason)
            if self._program_builder.mesh is not None:
                # one-time mesh gauges: a scraped metrics page can divide
                # any aggregate number down to per-chip without the manifest
                mesh_shape = dict(self._program_builder.mesh.shape)
                obs.registry.gauge(
                    "fl_mesh_devices",
                    help="devices backing the round-program mesh",
                ).set(float(self._program_builder.n_devices))
                obs.registry.gauge(
                    "fl_mesh_client_axis",
                    help="size of the 'clients' (data-parallel) mesh axis",
                ).set(float(self._program_builder.client_axis_size))
                obs.registry.gauge(
                    "fl_mesh_model_axis",
                    help="size of the 'model' (tensor-parallel) mesh axis",
                ).set(float(mesh_shape.get("model", 1)))
            # run manifest (served live at /manifest when http_port is set,
            # exported as manifest.json): provenance that makes a scraped
            # metrics page interpretable — versions, chip, mode, config hash
            try:
                extra = None
                if self._resume_info is not None:
                    # resumed runs disclose where they picked up — the key
                    # is absent on fresh runs so legacy manifests are stable
                    extra = {"resume": dict(self._resume_info)}
                obs.update_manifest(run_manifest(
                    execution_mode=mode,
                    execution_mode_reason=mode_reason,
                    donation=bool(_donate_argnums(0, 1)),
                    mesh=self._program_builder.descriptor(),
                    config=self._manifest_config(n_rounds),
                    extra=extra,
                ))
            except Exception:
                logging.getLogger(__name__).warning(
                    "run manifest construction failed", exc_info=True
                )
            if obs.introspection and n_rounds >= 1 and not self._async_active:
                # compiled-program introspection at BUILD time: XLA
                # cost/memory analysis, compile wall, cache attribution —
                # zero per-round cost, measured MFU for every round record,
                # and once per compiled program: a later fit() on the same
                # programs records the remembered reports (``cached=``).
                # (Async runs skip it: the event programs' work varies with
                # the consumed buffer, so a single per-round FLOP number
                # would be dishonest — staleness/cadence metrics carry the
                # async story instead.)
                with obs.span("introspect", cat="fit") as intro_span:
                    intro = obs.introspector
                    hits0, misses0 = intro.hits, intro.misses
                    # the chunked path dispatches checkpoint_every-round
                    # chunks when a snapshot checkpointer is attached —
                    # introspect the program shape fit() will actually run
                    self._introspect_programs(
                        mode, self._rounds_per_dispatch(n_rounds, start_round)
                    )
                    hits = intro.hits - hits0
                    intro_span.set(
                        cached=f"{hits}/{hits + intro.misses - misses0}"
                    )
        if flight is not None:
            # run-level provenance for the bundle header ("run" in
            # ring.msgpack): what was executing when the box was opened
            facts: dict[str, Any] = {
                "execution_mode": mode,
                "execution_mode_reason": mode_reason,
                "n_rounds": n_rounds,
                "start_round": start_round,
                "config_hash": obs.manifest.get("config_hash"),
            }
            if self._cohort_active:
                facts["cohort_slots"] = self.n_clients
                facts["registry_size"] = self.registry_size
            if self._async_active:
                facts["async"] = True
            flight.set_run_facts(**facts)
        for r in self.reporters:
            r.report({"host_type": "server", "fit_start": time.time(),
                      "num_rounds": n_rounds, "execution_mode": mode,
                      "execution_mode_reason": mode_reason})
        self._sigterm_round = None
        if (mode == EXEC_CHUNKED or self._cohort_active
                or (self._async_active and n_rounds >= 1)):
            # every driver but the dense per-round one (_fit_pipelined)
            self._end_prologue()

        def _note_sigterm() -> None:
            # runs INSIDE the signal handler: the round the run was at
            # when SIGTERM arrived — the teardown drains that follow may
            # legitimately record later rounds, but the verdict names
            # this. LOCK-FREE read: the handler can interrupt the very
            # thread holding the recorder lock (chunked-mode epilogues
            # record on the main thread) — taking it here would deadlock.
            if flight is not None:
                self._sigterm_round = flight.last_round_hint

        try:
            # SIGTERM trap (flight recorder armed only): a preemption
            # becomes a SigtermShutdown raised in the main thread, so the
            # except below publishes the black box and every finally
            # (checkpoint flush, consumer close) still runs — then the
            # process exits with the conventional 143.
            with (trap_sigterm(on_signal=_note_sigterm)
                  if flight is not None else contextlib.nullcontext()):
                if self._async_active and n_rounds >= 1:
                    self._fit_async(n_rounds, mode, plan, start_round)
                elif self._cohort_active:
                    # both routes handle n_rounds < 1 themselves (graceful
                    # no-op) — the dense pipelined fallback would touch
                    # the absent banks
                    if mode == EXEC_CHUNKED:
                        self._fit_cohort_chunked(n_rounds, start_round)
                    else:
                        self._fit_cohort(n_rounds, start_round)
                elif mode == EXEC_CHUNKED:
                    self._fit_chunked(n_rounds, start_round)
                else:
                    self._fit_pipelined(n_rounds, start_round)
        except BaseException as e:
            # ANY abnormal end — TrainingHealthError/ClientFailuresError/
            # QuorumError, an unhandled exception, a SIGTERM — publishes a
            # self-contained postmortem bundle BEFORE obs.shutdown() below
            # clears the trace/event evidence. Never masks the original
            # failure.
            self._end_prologue()
            self._dump_postmortem(e)
            raise
        finally:
            self._end_prologue()  # a call that ran no round
            # shutdown (not just export) ALWAYS runs — even when a round
            # raises (ClientFailuresError): it detaches the compile monitor
            # and releases/clears the tracer this run enabled, so a retry in
            # the same process doesn't double-count compiles, and the failed
            # run's trace/metrics (the run you most want to inspect) still
            # land on disk.
            artifacts = obs.shutdown()
        for rep in self.reporters:
            if artifacts:
                rep.report({"observability_artifacts": dict(artifacts)})
            rep.report({"fit_end": time.time()})
            rep.shutdown()
        return self.history

    def _manifest_config(self, n_rounds: int) -> dict:
        """JSON-able run-config facts for the manifest's ``config_hash`` —
        the experiment identity two scrapes can be matched on."""
        config = {
            "n_clients": self.n_clients,
            "batch_size": self.batch_size,
            "local_epochs": self.local_epochs,
            "local_steps": self.local_steps,
            "n_rounds": n_rounds,
            "strategy": type(self.strategy).__name__,
            "exchanger": type(self.exchanger).__name__,
            "client_manager": type(self.client_manager).__name__,
            "execution_mode": self.execution_mode,
            "telemetry": self._telemetry_enabled,
            "compression": (self.compression.describe()
                            if self._compression_active else None),
            # precision identity: an f32 and a bf16 run of the same recipe
            # are different experiments — and the dtype the manifest names
            # is the one the fl_program_*/MFU numbers were produced under
            "precision": (self.precision.describe()
                          if self._precision_active else None),
        }
        if self._cohort_active:
            # cohort-slot identity belongs in the config hash (a slot run
            # and a dense run are different programs; resume templates are
            # sized by the slot count); key absent on dense builds so
            # legacy hashes stay stable
            config["cohort"] = {
                "slots": self.cohort_config.slots,
                "registry_size": self.registry_size,
            }
        if self._async_active:
            # async identity belongs in the config hash (a buffered-async
            # and a synchronous run of the same recipe are different
            # experiments); key absent on sync builds so legacy hashes
            # stay stable
            config["async"] = self.async_config.describe()
        if self._program_builder.mesh is not None:
            # mesh identity belongs in the config hash (a sharded and an
            # unsharded run of the same recipe are different experiments);
            # key absent on single-chip builds so legacy hashes are stable
            config["mesh"] = self._program_builder.descriptor()
        return config

    # -- crash-consistent checkpoint/resume ------------------------------
    def _resume_config_hash(self) -> str:
        """The resume-relevant experiment identity a checkpoint binds to:
        the manifest config minus the knobs that may legitimately differ
        between an interrupted run and its resume — ``n_rounds`` (resuming
        with more rounds is the point), ``execution_mode`` (trajectories
        are pinned identical across modes, so cross-mode resume is legal),
        ``telemetry`` (observability never changes the trajectory) and
        ``mesh`` (placement, not math — restored arrays are re-sharded
        onto whatever mesh the resuming run deploys)."""
        cfg = {
            k: v for k, v in self._manifest_config(0).items()
            if k not in ("n_rounds", "execution_mode", "telemetry", "mesh")
        }
        return config_hash(cfg)

    def adopt_restored_state(self, server_state, client_states,
                             pending=None) -> None:
        """Install restored (host numpy) trees as the live training state.
        Under a mesh the arrays are ``device_put`` back onto the round
        programs' ``NamedSharding``s — the same placement a fresh build
        pins via in_shardings — so the first resumed dispatch never pays an
        implicit gather-and-reshard; single-chip runs get one committed
        device transfer instead of a per-dispatch host upload."""
        b = self._program_builder
        if b.mesh is not None:
            server_state = b.put(server_state, self._sh_server_state)
            client_states = b.put(client_states, self._sh_client_states)
            if pending is not None:
                pending = b.put(pending, b.client_sharding())
        else:
            server_state = jax.device_put(server_state)
            client_states = jax.device_put(client_states)
            if pending is not None:
                pending = jax.device_put(pending)
        self.server_state = server_state
        self.client_states = client_states
        if pending is not None:
            self._async_pending = pending

    def _ckpt_every(self) -> int | None:
        """The attached snapshot checkpointer's save cadence in rounds
        (None when no snapshot-capable checkpointer is attached)."""
        sc = self.state_checkpointer
        if sc is None or not hasattr(sc, "save_simulation_snapshot"):
            return None
        return max(int(getattr(sc, "checkpoint_every", 1) or 1), 1)

    def _rounds_per_dispatch(self, n_rounds: int, start_round: int = 1) -> int:
        """Scan length of the chunked path's next dispatch: all remaining
        rounds, capped at ``checkpoint_every`` when snapshots are due at
        chunk boundaries."""
        remaining = max(n_rounds - start_round + 1, 1)
        every = self._ckpt_every()
        return remaining if every is None else min(every, remaining)

    def _checkpoint_due(self, rnd: int) -> bool:
        every = self._ckpt_every()
        if every is None:
            return False
        return rnd % every == 0 or rnd >= self._fit_n_rounds

    def _async_pending_template(self, val_batches):
        """Host-shaped template of the async ``pending`` buffer (the tree
        the prologue produces), via ``jax.eval_shape`` — no device work, no
        prologue dispatch — for deserializing a restored buffer into."""
        prologue, _ = self._build_async_fns(self._telemetry_enabled)
        batches1 = self._round_batches(1)
        _states_sds, pending_sds = jax.eval_shape(
            prologue, self.server_state, self.client_states, batches1,
            val_batches,
        )
        return jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), pending_sds
        )

    def _maybe_resume(self, n_rounds: int, plan=None) -> int:
        """Bind the checkpointer to this run (config hash + metrics hook)
        and restore the newest good generation when one exists. Returns the
        first round/event to run (1 on a fresh start). Sets
        ``self._resume_info`` for the manifest's ``resume`` descriptor."""
        self._resume_info = None
        sc = self.state_checkpointer
        if sc is None:
            return 1
        # bind the frame's config hash + fl_ckpt_* metrics hook once —
        # explicit user-set values win
        if getattr(sc, "config_hash", "absent") is None:
            sc.config_hash = self._resume_config_hash()
        if getattr(sc, "on_save", "absent") is None:
            sc.on_save = self._emit_checkpoint_stats
        if not (hasattr(sc, "exists") and sc.exists()):
            return 1
        if self._async_active:
            if n_rounds < 1:
                return 1
            val_batches, _ = self._val_batches()
            template = self._async_pending_template(val_batches)
            start = sc.load_async_simulation(self, template, plan)
        elif self._cohort_active:
            # cohort resume: slot states + the registry's dirty rows —
            # every participated client's persistent state survives
            start = sc.load_cohort_simulation(self)
        elif hasattr(sc, "load_simulation"):
            # fit_with_per_round_checkpointing resume (base_server.py:143-229)
            start = sc.load_simulation(self)
        else:
            return 1
        info = getattr(sc, "last_restore_info", None)
        self._resume_info = {
            "next_round": int(start),
            "kind": ("async" if self._async_active
                     else "cohort" if self._cohort_active else "sync"),
        }
        if info is not None:
            self._resume_info.update(
                path=info.path, generation=info.generation,
                bytes=info.nbytes,
                fallback_skipped=list(info.fallback_skipped),
            )
        obs = self.observability
        if obs.enabled:
            reg = obs.registry
            reg.counter(
                "fl_ckpt_restores_total",
                help="state-checkpoint restores (resumed runs)",
            ).inc()
            if info is not None and info.fallback_skipped:
                reg.counter(
                    "fl_ckpt_fallbacks_total",
                    help="corrupt checkpoint generations skipped by the "
                         "retention-ring fallback at restore",
                ).inc(len(info.fallback_skipped))
            obs.log_event("resume", **self._resume_info)
        logging.getLogger(__name__).info(
            "resumed from checkpoint: next %s %d",
            "event" if self._async_active else "round", start,
        )
        return start

    def _emit_checkpoint_stats(self, stats: dict) -> None:
        """``fl_ckpt_*`` metrics + one ``checkpoint`` JSONL event per
        durable save. Runs on whichever thread persisted the frame (the
        async writer under the pipelined loop) — the registry is
        thread-safe and this hook never raises into the writer."""
        obs = self.observability
        if not obs.enabled:
            return
        reg = obs.registry
        reg.counter(
            "fl_ckpt_writes_total", help="durable state-checkpoint writes",
        ).inc()
        reg.counter(
            "fl_ckpt_bytes_written_total",
            help="bytes of durable state-checkpoint frames written",
        ).inc(int(stats.get("bytes", 0)))
        reg.counter(
            "fl_ckpt_write_seconds_total",
            help="wall seconds spent serializing+writing state checkpoints "
                 "(off the round loop under the async writer)",
        ).inc(float(stats.get("write_s", 0.0)))
        reg.gauge(
            "fl_ckpt_last_write_ms",
            help="wall milliseconds of the most recent checkpoint write",
        ).set(float(stats.get("write_s", 0.0)) * 1000.0)
        reg.gauge(
            "fl_ckpt_generation",
            help="newest durable checkpoint generation in the retention ring",
        ).set(float(stats.get("generation", 0)))
        reg.log_event(
            "checkpoint",
            round=stats.get("round"),
            generation=stats.get("generation"),
            bytes=stats.get("bytes"),
            write_ms=round(float(stats.get("write_s", 0.0)) * 1000.0, 3),
            path=stats.get("path"),
            kind=stats.get("kind", "sync"),
        )
        flight = obs.flight_recorder
        if flight is not None:
            # the bundle's "what to resume from": newest durable generation
            flight.note_checkpoint(stats)

    def _dump_postmortem(self, exc: BaseException) -> None:
        """Best-effort postmortem bundle for an abnormal ``fit()`` end
        (``observability/bundle.py``): classify ``exc`` into a verdict,
        publish ``postmortem_<ts>/`` under the observability output dir,
        and flip ``/healthz`` to 503. By the time the exception reaches
        here the fit paths' ``finally`` blocks have closed the
        RoundConsumer (draining pending epilogues into the flight ring)
        and flushed the checkpoint writer — the ring is as complete as the
        process can make it. NEVER raises: the primary failure propagates
        untouched."""
        obs = self.observability
        if not obs.enabled or obs.output_dir is None:
            return
        try:
            from fl4health_tpu.observability.bundle import (
                verdict_from_exception,
            )

            verdict = verdict_from_exception(
                exc, recorder=obs.flight_recorder
            )
            if (verdict.get("kind") == "sigterm"
                    and getattr(self, "_sigterm_round", None) is not None):
                # the handler's snapshot wins over the recorder's current
                # last round: drains during unwind may have run past it
                verdict["round"] = self._sigterm_round
            if getattr(self, "_last_epilogue_round", None) is not None:
                # pipelined runs: which round's epilogue last FINISHED —
                # evidence beyond it died with the in-flight rounds
                verdict["epilogues_through_round"] = (
                    self._last_epilogue_round
                )
            path = obs.dump_bundle(verdict)
            if path:
                obs.log_event(
                    "postmortem", path=path,
                    kind=verdict.get("kind"), round=verdict.get("round"),
                )
                logging.getLogger(__name__).warning(
                    "abnormal end (%s) — postmortem bundle published at %s",
                    verdict.get("kind"), path,
                )
        except Exception:
            logging.getLogger(__name__).warning(
                "postmortem bundle dump failed (the primary exception "
                "propagates)", exc_info=True,
            )

    def _close_ckpt_writer(self, writer) -> None:
        """Close the async checkpoint writer on EVERY exit path and surface
        its stored failure without masking an in-flight exception.
        ``close()`` drains the queue before joining, so a run that halts
        (``TrainingHealthError``, ``ClientFailuresError``) still publishes
        its last completed-round checkpoint before the error propagates."""
        writer.close()
        in_flight = sys.exc_info()[1] is not None
        try:
            writer.raise_pending()
        except BaseException:
            if not in_flight:
                raise
            logging.getLogger(__name__).warning(
                "checkpoint write failed during error shutdown (the "
                "primary exception propagates)", exc_info=True,
            )

    @contextlib.contextmanager
    def _ckpt_writer_scope(self, active: bool,
                           attach_model_ckpts: bool = False):
        """THE async-checkpoint-writer lifecycle, shared by every fit path:
        yields a fresh :class:`AsyncCheckpointWriter` (or None when
        ``active`` is False), flushes it on clean exit, and on EVERY exit —
        error paths included — drains+closes it, surfaces stored write
        failures without masking an in-flight exception
        (:meth:`_close_ckpt_writer`), detaches any model checkpointers and
        resets ``self._ckpt_writer``."""
        if not active:
            yield None
            return
        writer = self._ckpt_writer = AsyncCheckpointWriter()
        attached = []
        if attach_model_ckpts:
            for _mode, ckpt in self.model_checkpointers:
                if hasattr(ckpt, "async_writer"):
                    ckpt.async_writer = writer
                    attached.append(ckpt)
        try:
            yield writer
            writer.flush()  # clean exit: every submitted write is durable
        finally:
            try:
                self._close_ckpt_writer(writer)
            finally:
                for ckpt in attached:
                    ckpt.async_writer = None
                self._ckpt_writer = None

    def _introspect_programs(self, mode: str, n_rounds: int) -> None:
        """Capture XLA cost/memory analysis for the round programs this
        ``fit()`` will dispatch (``observability/introspect.py``).

        Lowering happens against abstract ``ShapeDtypeStruct`` args, so no
        device work runs and the training trajectory cannot change; the
        compile goes through XLA's cached-compile path, so with the
        persistent compilation cache the later jit dispatch of the same
        program is a disk hit, not a second backend compile (without the
        cache this is one extra build-time compile per program — never a
        per-round cost). Every ``fit()`` asks about every program, and the
        introspector answers from its remembered report when the jitted
        object, the abstract arguments and the descriptors are those of
        its last capture (a second ``fit()`` on this simulation; a chunk
        of another length or rebuilt programs are captured afresh), so the
        reports, gauges, ``program`` events and ``_round_program_flops``
        of each call read the same either way. Failures degrade to a
        warning: introspection must not take down a run."""
        obs = self.observability
        intro = obs.introspector
        mesh_desc = self._program_builder.descriptor()
        prec_desc = (self.precision.describe() if self._precision_active
                     else None)
        try:
            if self._cohort_active:
                # slot programs lower against ABSTRACT slot shapes — by
                # construction a function of (slots, step budgets, batch,
                # example shape), never of the registry size: the
                # fl_program_* flops/peak-HBM numbers ARE the O(K) proof
                # (pinned across registry sizes by tests)
                aa = self.registry.abstract_round_args(self.n_clients)
                r = jnp.asarray(1, jnp.int32)
                t = self._telemetry_enabled
                fit_fn = self._fit_round_t if t else self._fit_round
                eval_fn = self._eval_round_t if t else self._eval_round
                fit_name = "fit_round_t" if t else "fit_round"
                eval_name = "eval_round_t" if t else "eval_round"
                intro.introspect_jit(
                    fit_name, fit_fn,
                    (self.server_state, self.client_states, aa["batches"],
                     aa["mask"], r, aa["val_batches"],
                     aa["sample_counts"]),
                    mesh=mesh_desc, precision=prec_desc,
                )
                intro.introspect_jit(
                    eval_name, eval_fn,
                    (self.server_state, self.client_states,
                     aa["val_batches"], aa["val_counts"]),
                    mesh=mesh_desc, precision=prec_desc,
                )
                self._round_program_flops = intro.round_flops(
                    (fit_name, eval_name)
                )
                if mode == EXEC_CHUNKED:
                    # the chunk scan program too: its report carries the
                    # per-dispatch facts (rounds_per_dispatch, the
                    # in-graph draw site) the O(rounds/R) claim quotes
                    kd = self._rounds_per_dispatch(n_rounds)
                    ca = self.registry.abstract_chunk_args(
                        self.n_clients, kd
                    )
                    w = ca["window_ids"].shape[0]
                    as_window = lambda t: jax.tree_util.tree_map(  # noqa: E731
                        lambda a: jax.ShapeDtypeStruct(
                            (w,) + jnp.shape(a)[1:], jnp.result_type(a)
                        ), t,
                    )
                    w_client = as_window(self.client_states)
                    w_srows = (
                        as_window(self.strategy.state_rows(
                            self.server_state
                        ))
                        if self.registry.has_strategy_rows else {}
                    )
                    intro.introspect_jit(
                        "fit_cohort_chunk", self._make_cohort_chunk(),
                        (self.server_state, self.client_states, w_client,
                         w_srows, self.rng, ca["window_ids"],
                         ca["batches"], ca["mask"], ca["sample_counts"],
                         ca["val_batches"], ca["val_counts"], r),
                        rounds_per_dispatch=kd, cohort_draw="in_graph",
                        mesh=mesh_desc, precision=prec_desc,
                    )
                intro.hbm_headroom_bytes()
                return
            val_batches, val_counts = self._val_batches()
            mask = self.client_manager.sample(
                jax.random.fold_in(self.rng, 2000 + 1), 1
            )
            r = jnp.asarray(1, jnp.int32)
            test = self._test_batches()
            if mode == EXEC_CHUNKED:
                p_idx, p_em, p_sm = self._round_plan(1)

                def stacked_sds(a):
                    a1 = jnp.asarray(a)
                    return jax.ShapeDtypeStruct((n_rounds,) + a1.shape, a1.dtype)

                args = [self.server_state, self.client_states,
                        self._x_train_stack, self._y_train_stack,
                        stacked_sds(p_idx), stacked_sds(p_em),
                        stacked_sds(p_sm),
                        jax.ShapeDtypeStruct((n_rounds,) + mask.shape,
                                             mask.dtype),
                        r, val_batches, val_counts]
                if test is not None:
                    args.extend(test)
                intro.introspect_jit(
                    "fit_chunk_eval", self._make_chunked_fit_with_eval(),
                    tuple(args), rounds_per_dispatch=n_rounds,
                    mesh=mesh_desc, precision=prec_desc,
                )
                names: tuple[str, ...] = ("fit_chunk_eval",)
            else:
                idx, em, sm = self._round_plan(1)
                batches = jax.eval_shape(
                    engine.gather_batches, self._x_train_stack,
                    self._y_train_stack, idx, em, sm,
                )
                t = self._telemetry_enabled
                fit_fn = self._fit_round_t if t else self._fit_round
                eval_fn = self._eval_round_t if t else self._eval_round
                fit_name = "fit_round_t" if t else "fit_round"
                eval_name = "eval_round_t" if t else "eval_round"
                intro.introspect_jit(
                    fit_name, fit_fn,
                    (self.server_state, self.client_states, batches, mask,
                     r, val_batches),
                    mesh=mesh_desc, precision=prec_desc,
                )
                intro.introspect_jit(
                    eval_name, eval_fn,
                    (self.server_state, self.client_states, val_batches,
                     val_counts),
                    mesh=mesh_desc, precision=prec_desc,
                )
                names = (fit_name, eval_name)
                if test is not None:
                    # same eval program, test-split shapes -> its own
                    # executable, so it gets its own report
                    test_name = eval_name + "_test"
                    intro.introspect_jit(
                        test_name, eval_fn,
                        (self.server_state, self.client_states,
                         test[0], test[1]),
                        mesh=mesh_desc, precision=prec_desc,
                    )
                    names = names + (test_name,)
            self._round_program_flops = intro.round_flops(names)
            intro.hbm_headroom_bytes()
        except Exception:
            logging.getLogger(__name__).warning(
                "compiled-program introspection failed (continuing without "
                "measured MFU)", exc_info=True,
            )

    # -- pipelined per-round path --------------------------------------
    def _fit_pipelined(self, n_rounds: int, start_round: int = 1) -> None:
        """The per-round path, pipelined: each round the producer (this
        thread) dispatches fit+eval and hands the round's results — one
        fused device tree plus any host snapshots donation would otherwise
        invalidate — to a background RoundConsumer that runs the host
        epilogue for round r while the device executes round r+1. The next
        round's batches are prefetched concurrently. ``start_round`` > 1
        continues a restored run (``_maybe_resume``)."""
        obs = self.observability
        with obs.span("setup", cat="fit"):
            val_batches, val_counts = self._val_batches()
        self._fit_n_rounds = n_rounds
        # the round program donates the states — break any Python-level
        # buffer aliasing once; round outputs stay alias-free thereafter
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        # the writer scope flushes on clean exit and, on error exits,
        # drains + surfaces write failures without masking the in-flight
        # exception — a halted run still publishes its last
        # completed-round checkpoint
        with self._ckpt_writer_scope(
            bool(self.model_checkpointers
                 or self.state_checkpointer is not None),
            attach_model_ckpts=True,
        ):
            consumer = self._consumer = RoundConsumer(
                maxsize=self.pipeline_depth
            )
            # per-round data staging is SHARDED under a mesh: the
            # prefetcher's device_put splits the gathered [C, ...] batch
            # stack over the clients axis while the previous round still
            # runs
            prefetcher = self._prefetcher = RoundPrefetcher(self)
            try:
                if start_round <= n_rounds:
                    prefetcher.schedule(start_round)
                for rnd in range(start_round, n_rounds + 1):
                    consumer.raise_pending()
                    # opt-in XProf capture of ONE round (profile_round_idx)
                    with obs.maybe_profile(rnd):
                        self._run_round(rnd, val_batches, val_counts)
                consumer.flush()  # barrier: every round's epilogue has run
            finally:
                consumer.close()
                prefetcher.close()
                # retained for the postmortem verdict: which round's host
                # epilogue last FINISHED before this run ended
                self._last_epilogue_round = consumer.last_completed_round
                self._consumer = None
                self._prefetcher = None

    def _run_round(self, rnd: int, val_batches, val_counts) -> None:
        """Producer half of one federated round: configure_fit -> fit
        dispatch -> eval dispatch, then submit the host epilogue
        (_finish_round) to the RoundConsumer. All device_get of results
        happens in the consumer (results are fresh outputs, never donated
        into a later round, so they stay valid); only checkpoint/state
        snapshots — whose buffers round r+1's donation WILL invalidate —
        are pulled here."""
        obs = self.observability
        consumer = self._consumer
        prefetcher = self._prefetcher
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            # compile accounting baseline: delta over the round = recompiles
            # (shape drift re-paying XLA compiles is THE classic round-loop bug)
            compiles_before = obs.registry.counter("jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total"
            ).value
        device_wait_s = 0.0
        self._end_prologue()
        t0 = time.time()
        with obs.span("round", round=rnd):
            with obs.span("configure_fit", round=rnd):
                if self.train_data_provider is not None:
                    fresh = self.train_data_provider(rnd)
                    if fresh is not None:
                        self.set_train_data(*fresh)
                # admin-plane retunes land HERE — a per-round host boundary
                # before anything reads server_state, after the provider (so
                # a submit issued synchronously from it applies this round)
                self._apply_admin_retunes(rnd)
                mask = self.client_manager.sample(
                    jax.random.fold_in(self.rng, 2000 + rnd), rnd
                )
                if obs.watchdog is not None:
                    # host-side mitigation (HealthPolicy action="mitigate"):
                    # clients the watchdog quarantined are sampled out of
                    # later rounds. None while nothing is quarantined, so
                    # the un-mitigated mask values stay untouched. With the
                    # pipeline running depth rounds ahead, a new quarantine
                    # takes effect once the producer catches up (pipelined
                    # path only — in-graph quarantine covers the chunked
                    # scan, resilience/quarantine.py).
                    keep = obs.watchdog.quarantine_keep_mask(self.n_clients)
                    if keep is not None:
                        mask = mask * jnp.asarray(keep, jnp.float32)
                # recovery-supervisor quarantine (resilience/supervisor.py):
                # suspects a past engagement named stay sampled out until
                # their release round; a pass-through when idle
                mask = self._apply_recovery_keep(mask, rnd)
                batches = (prefetcher.take(rnd) if prefetcher is not None
                           else self._round_batches(rnd))
            if prefetcher is not None and rnd < self._fit_n_rounds:
                # stage round r+1's plan+gather while round r executes
                prefetcher.schedule(rnd + 1)
            telemetry = None
            with obs.span("fit_round", round=rnd) as fit_span:
                # `dispatch` is what the enqueue costs the host; the fence
                # below is the wait for the device
                with obs.span("dispatch", round=rnd, program="fit"):
                    if self._telemetry_enabled:
                        (
                            self.server_state,
                            self.client_states,
                            fit_losses,
                            fit_metrics,
                            per_client_fit_losses,
                            telemetry,
                        ) = self._fit_round_t(
                            self.server_state, self.client_states, batches,
                            mask, jnp.asarray(rnd, jnp.int32), val_batches,
                        )
                    else:
                        (
                            self.server_state,
                            self.client_states,
                            fit_losses,
                            fit_metrics,
                            per_client_fit_losses,
                        ) = self._fit_round(
                            self.server_state, self.client_states, batches,
                            mask, jnp.asarray(rnd, jnp.int32), val_batches,
                        )
                # Honest device time: the dispatch above returns at enqueue;
                # fence (enabled path ONLY — disabled adds zero syncs) so the
                # span covers actual device execution, not enqueue latency.
                _, wait = obs.fence(
                    (fit_losses, fit_metrics, per_client_fit_losses)
                )
                device_wait_s += wait
                fit_span.set(device_wait_s=wait)
            need_pre = any(m == CheckpointMode.PRE_AGGREGATION
                           for m, _ in self.model_checkpointers)
            need_post = any(m == CheckpointMode.POST_AGGREGATION
                            for m, _ in self.model_checkpointers)
            # snapshot only on due rounds (checkpoint_every cadence): the
            # device-side copies + fused pull of two full state trees are
            # the entire per-round cost of durable state, so off-cadence
            # rounds skip them entirely
            snapshot_state = (
                self.state_checkpointer is not None
                and hasattr(self.state_checkpointer, "save_simulation_snapshot")
                and self._checkpoint_due(rnd)
            )
            pre_agg_params = None
            if need_pre:
                # post-fit client-stacked params (client_module.py:23-28
                # PRE_AGGREGATION semantics) — DEVICE-side copy (async, no
                # host sync) taken BEFORE eval overwrites the stack with the
                # pulled globals; the copy's fresh buffers are never donated,
                # so the consumer's fused transfer can pull them later
                with obs.span("state_snapshot", round=rnd, what="pre_agg"):
                    pre_agg_params = jax.tree_util.tree_map(
                        jnp.copy, self.client_states.params
                    )
            t1 = time.time()
            with obs.span("eval_round", round=rnd) as eval_span:
                with obs.span("dispatch", round=rnd, program="eval"):
                    if self._telemetry_enabled:
                        (
                            self.client_states,
                            eval_losses,
                            eval_metrics,
                            per_client_eval_losses,
                            per_client_eval_metrics,
                            ev_nonfinite,
                        ) = self._eval_round_t(
                            self.server_state, self.client_states,
                            val_batches, val_counts,
                        )
                        telemetry = telemetry.replace(
                            nonfinite_eval_loss=ev_nonfinite
                        )
                    else:
                        (
                            self.client_states,
                            eval_losses,
                            eval_metrics,
                            per_client_eval_losses,
                            per_client_eval_metrics,
                        ) = self._eval_round(
                            self.server_state, self.client_states,
                            val_batches, val_counts,
                        )
                self.server_state = self.strategy.update_after_eval(
                    self.server_state, per_client_eval_losses,
                    per_client_eval_metrics, mask
                )
                _, eval_wait = obs.fence((eval_losses, eval_metrics))
                test = self._test_batches()
                test_losses = test_metrics = None
                if test is not None:
                    # Separate test loader: same aggregated model, "test - "
                    # prefixed keys alongside the val metrics
                    # (base_server.py:545). The returned stack is
                    # value-identical to the val-eval one (pull is
                    # idempotent) but must be re-assigned: the input stack
                    # was donated.
                    with obs.span("dispatch", round=rnd, program="test"):
                        ev = (self._eval_round_t if self._telemetry_enabled
                              else self._eval_round)(
                            self.server_state, self.client_states,
                            test[0], test[1]
                        )
                    self.client_states, test_losses, test_metrics = ev[:3]
                    # fence the test dispatch too — its device time belongs
                    # in device_wait_s, not misattributed to host_s
                    _, test_wait = obs.fence((test_losses, test_metrics))
                    eval_wait += test_wait
                device_wait_s += eval_wait
                eval_span.set(device_wait_s=eval_wait)
            post_agg_params = None
            state_trees = None
            if need_post or snapshot_state:
                # device-side copies only (async): the producer never blocks
                # on a transfer — the consumer's fused device_get pulls these
                # fresh (never-donated) buffers off-thread
                with obs.span("state_snapshot", round=rnd, what="post_agg"):
                    if need_post:
                        post_agg_params = jax.tree_util.tree_map(
                            jnp.copy, self.global_params
                        )
                    if snapshot_state:
                        state_trees = jax.tree_util.tree_map(
                            jnp.copy,
                            {"server_state": self.server_state,
                             "client_states": self.client_states},
                        )
            t2 = time.time()
            compiles_after = compile_s_after = None
            if obs.enabled:
                # all of round r's compiles happened at dispatch, above; read
                # the counters HERE so a pipelined consumer can't misattribute
                # round r+1's (hypothetical) recompile to round r
                compiles_after = obs.registry.counter(
                    "jax_backend_compiles_total").value
                compile_s_after = obs.registry.counter(
                    "jax_backend_compiles_seconds_total").value
            device_results = {
                "mask": mask,
                "fit_losses": fit_losses,
                "fit_metrics": fit_metrics,
                "per_client_fit_losses": per_client_fit_losses,
                "eval_losses": eval_losses,
                "eval_metrics": eval_metrics,
            }
            if telemetry is not None:
                # the RoundTelemetry pytree rides the SAME fused transfer —
                # in-graph observability adds zero extra host syncs
                device_results["telemetry"] = telemetry
            q_fn = getattr(self.strategy, "quarantine_mask", None)
            if q_fn is not None and obs.enabled:
                # in-graph quarantine visibility: device-side copy (the
                # server-state buffer will be donated into the next round)
                # riding the consumer's fused transfer; quarantine itself
                # lives in the strategy and needs no observability
                device_results["_quarantine"] = jnp.copy(
                    q_fn(self.server_state)
                )
            if test_losses is not None:
                device_results["test_losses"] = test_losses
                device_results["test_metrics"] = test_metrics
            # snapshots ride the SAME fused transfer (keys the consumer pops
            # before the results are read)
            if pre_agg_params is not None:
                device_results["_pre_agg_params"] = pre_agg_params
            if post_agg_params is not None:
                device_results["_post_agg_params"] = post_agg_params
            if state_trees is not None:
                device_results["_state_trees"] = state_trees
            work = _RoundWork(
                round=rnd,
                device_results=device_results,
                fit_elapsed_s=t1 - t0,
                eval_elapsed_s=t2 - t1,
                device_wait_s=device_wait_s,
                compiles_before=compiles_before,
                compile_s_before=compile_s_before,
                compiles_after=compiles_after,
                compile_s_after=compile_s_after,
            )
            if consumer is not None:
                consumer.submit_round(
                    rnd, functools.partial(self._finish_round, work))
                legacy_state_save = (
                    self.state_checkpointer is not None
                    and not hasattr(self.state_checkpointer,
                                    "save_simulation_snapshot")
                )
                if legacy_state_save or not self.failure_policy.accept_failures:
                    # Correctness over overlap, two cases:
                    # - legacy sim-based checkpointer API (save_simulation
                    #   only): it reads LIVE sim state + history, so the
                    #   producer must not run ahead of the save;
                    # - accept_failures=False: the failure screen runs in the
                    #   epilogue and must be able to terminate BEFORE the
                    #   next round dispatches/mutates state, exactly like the
                    #   old inline loop.
                    consumer.flush()
            else:
                # no pipeline (direct calls in tests) — run inline
                self._finish_round(work)

    def _finish_round(self, work: "_RoundWork") -> None:
        """Consumer half of one round, as one ``epilogue`` span: from the
        fused device->host transfer to the reporters."""
        with self.observability.span("epilogue", round=work.round):
            self._round_epilogue(work)

    def _round_epilogue(self, work: "_RoundWork") -> None:
        """ONE fused device->host transfer of the results tree, then
        failure-policy screen, checkpoint decisions, RoundRecord
        construction and reporter I/O — all while the device executes later
        rounds. Runs on the RoundConsumer thread in submission (= round)
        order."""
        obs = self.observability
        rnd = work.round
        # the single fused pull this round pays (replaces ~8 scattered
        # device_get/float() syncs in the old loop)
        host = jax.device_get(work.device_results)
        mask = np.asarray(host["mask"])
        pre_agg_params = host.pop("_pre_agg_params", None)
        post_agg_params = host.pop("_post_agg_params", None)
        state_trees = host.pop("_state_trees", None)
        quarantine_mask = host.pop("_quarantine", None)
        registry_rows = host.pop("_registry_rows", None)
        cohort_info = work.cohort_info
        if registry_rows is not None:
            # cohort-slot rounds: the updated rows came down on the SAME
            # fused pull; scatter them under their registry ids, then
            # release the producer (it gates the next round's state gather
            # on this event)
            meta = work.cohort_meta
            with obs.span("registry_scatter", round=rnd,
                          valid=meta["valid"]) as sc_span:
                s0 = time.perf_counter()
                self.registry.scatter(
                    meta["idx"], meta["valid"],
                    registry_rows["client_states"],
                    registry_rows.get("strategy_rows"),
                )
                scatter_ms = (time.perf_counter() - s0) * 1e3
                sc_span.set(scatter_ms=scatter_ms)
            meta["scatter_event"].set()
            cohort_info = {
                "cohort_slots": meta["slots"],
                "cohort_valid": meta["valid"],
                "registry_size": meta["registry_size"],
                "registry_dirty_rows": self.registry.dirty_rows,
                "stage_ms": round(meta["stage_ms"], 3),
                "gather_ms": round(meta["gather_ms"], 3),
                "scatter_ms": round(scatter_ms, 3),
                "staged_bytes": meta["staged_bytes"],
                # host-barrier accounting: how many rounds this dispatch
                # amortized (1 on the per-round path) and where the cohort
                # draw ran — the O(rounds/R) claim, measured per round
                "rounds_per_dispatch": meta.get("rounds_per_dispatch", 1),
                "cohort_draw": meta.get("cohort_draw", "host"),
            }
        telemetry_obj = host.pop("telemetry", None)
        telemetry_host = (
            {k: np.asarray(v) for k, v in telemetry_obj.as_dict().items()}
            if telemetry_obj is not None else None
        )
        with obs.span("aggregate", round=rnd):
            # Failure policy screen (base_server.py:316-318): terminate
            # before checkpointing a poisoned aggregate when
            # accept_failures=False.
            host_fit_losses = host["per_client_fit_losses"]
            try:
                failed = self.failure_policy.check(host_fit_losses, mask)
            except ClientFailuresError as cf:
                # verdict facts: the policy doesn't know the round, and
                # cohort rounds fail by SLOT — map to registry ids here,
                # while the round's cohort view is still in hand
                cf.round = rnd
                if work.cohort_meta is not None:
                    ids = np.asarray(work.cohort_meta["idx"])
                    cf.registry_clients = [
                        int(ids[c]) for c in cf.clients
                        if 0 <= int(c) < len(ids)
                    ]
                raise
            fit_losses = {k: float(v) for k, v in host["fit_losses"].items()}
            fit_metrics = {k: float(v) for k, v in host["fit_metrics"].items()}
            eval_losses = {k: float(v) for k, v in host["eval_losses"].items()}
            eval_metrics = {k: float(v) for k, v in host["eval_metrics"].items()}
            if "test_losses" in host:
                eval_losses.update({
                    f"test - {k}": float(v)
                    for k, v in host["test_losses"].items()
                })
                eval_metrics.update({
                    f"test - {k}": float(v)
                    for k, v in host["test_metrics"].items()
                })
        with obs.span("checkpoint", round=rnd, mode="pre_aggregation"):
            for mode, ckpt in self.model_checkpointers:
                if mode == CheckpointMode.PRE_AGGREGATION:
                    ckpt.maybe_checkpoint(
                        pre_agg_params,
                        fit_losses.get("backward", float("nan")),
                        fit_metrics,
                    )
        with obs.span("checkpoint", round=rnd, mode="post_aggregation"):
            for mode, ckpt in self.model_checkpointers:
                if mode == CheckpointMode.POST_AGGREGATION:
                    ckpt.maybe_checkpoint(
                        post_agg_params,
                        eval_losses.get("checkpoint", float("nan")),
                        eval_metrics,
                    )
        rec = RoundRecord(
            round=rnd,
            fit_losses=fit_losses,
            fit_metrics=fit_metrics,
            eval_losses=eval_losses,
            eval_metrics=eval_metrics,
            fit_elapsed_s=work.fit_elapsed_s,
            eval_elapsed_s=work.eval_elapsed_s,
        )
        self.history.append(rec)
        # fleet-ledger absorb BEFORE the state checkpoint below: the saved
        # frame's ledger must be as-of THIS round, or a resume at rnd+1
        # would undercount rnd's participation
        fleet_info = self._fleet_absorb_round(
            rnd, mask, host_fit_losses, telemetry_host,
            registry_ids=(np.asarray(work.cohort_meta["idx"])
                          if work.cohort_meta is not None else None),
            quarantine_mask=quarantine_mask,
            failed=failed,
            async_info=work.async_info,
        )
        if self.state_checkpointer is not None:
            # per-round durable state (_save_server_state, base_server.py:420)
            fleet_doc = self._fleet_snapshot_doc()
            with obs.span("checkpoint", round=rnd, mode="state"):
                if state_trees is not None:
                    if work.resume_meta is not None:
                        # buffered-async event snapshot: the trees include
                        # the pending buffer, plus the plan-prefix
                        # fingerprint + virtual clock the resume verifies
                        self.state_checkpointer.save_async_snapshot(
                            state_trees, rnd, self.n_clients,
                            list(self.history),
                            plan_fingerprint=work.resume_meta[
                                "plan_fingerprint"],
                            virtual_time_s=work.resume_meta[
                                "virtual_time_s"],
                            writer=self._ckpt_writer,
                            fleet=fleet_doc,
                        )
                    elif work.cohort_meta is not None:
                        # cohort snapshot: slot states + the registry's
                        # dirty rows (exported AFTER this round's scatter —
                        # the consumer is FIFO, so the rows are exactly
                        # through round rnd)
                        self.state_checkpointer.save_cohort_snapshot(
                            state_trees, rnd, self.n_clients,
                            self.registry_size,
                            self.registry.export_rows(),
                            list(self.history), writer=self._ckpt_writer,
                            fleet=fleet_doc,
                        )
                    else:
                        self.state_checkpointer.save_simulation_snapshot(
                            state_trees, rnd, self.n_clients,
                            list(self.history), writer=self._ckpt_writer,
                            fleet=fleet_doc,
                        )
                elif not hasattr(self.state_checkpointer,
                                 "save_simulation_snapshot"):
                    # legacy sim-based API: reads live sim state — safe ONLY
                    # because the producer flushes this round's epilogue
                    # before dispatching the next round (see _run_round)
                    self.state_checkpointer.save_simulation(self, rnd)
                # else: snapshot-capable checkpointer, off-cadence round —
                # nothing due
        obs_summary = None
        if obs.enabled:
            obs_summary = self._record_round_metrics(
                rnd, rec, mask, host_fit_losses, failed,
                work.compiles_before, work.compile_s_before,
                work.device_wait_s,
                compiles_after=work.compiles_after,
                compile_s_after=work.compile_s_after,
                telemetry=telemetry_host,
                async_info=work.async_info,
                cohort_info=cohort_info,
                fleet_info=fleet_info,
                # cohort rounds: the [K] registry ids the slots mapped to,
                # so the flight ring (and any postmortem ranking built on
                # it) attributes evidence to REAL clients, not slots
                registry_ids=(np.asarray(work.cohort_meta["idx"])
                              if work.cohort_meta is not None else None),
            )
        if quarantine_mask is not None:
            # cohort rounds report quarantine by REGISTRY id, not slot
            ids = (np.asarray(work.cohort_meta["idx"])
                   if work.cohort_meta is not None else None)
            self._emit_quarantine_metrics(
                rnd, np.asarray(quarantine_mask), ids=ids
            )
        with obs.span("report", round=rnd):
            for rep in self.reporters:
                payload = {
                    "fit_losses": rec.fit_losses,
                    "fit_metrics": rec.fit_metrics,
                    "eval_losses": rec.eval_losses,
                    "eval_metrics": rec.eval_metrics,
                    "fit_elapsed_s": rec.fit_elapsed_s,
                    "eval_elapsed_s": rec.eval_elapsed_s,
                    "execution_mode": self._active_execution_mode,
                }
                if obs_summary is not None:
                    # same data the registry/trace hold, bridged through
                    # ReportsManager so JsonReporter/WandBReporter see it
                    payload["observability"] = dict(obs_summary)
                rep.report(payload, round=rnd)
        # watchdog LAST: the round's record/metrics/reports always land
        # before a halt check tears the run down (the raise propagates to
        # the producer via the RoundConsumer's exception channel)
        if telemetry_host is not None and obs.watchdog is not None:
            obs.watchdog.observe(
                rnd, telemetry_host, mask,
                rec.fit_losses.get("backward", float("nan")),
                obs=obs, reporters=self.reporters,
            )
        # recovery probation: a round only counts healthy once the
        # watchdog passed it (a halt above skips this)
        self._note_recovery_round(rnd)

    # -- chunked on-device path ----------------------------------------
    def _fit_chunked(self, n_rounds: int, start_round: int = 1) -> None:
        """fit()'s chunked route: the rounds execute as compiled lax.scan
        dispatches (fit + eval per round on device), then ONE fused
        device->host pull per dispatch materializes the RoundRecords.
        Per-round host overhead collapses to the record/report loop at
        each chunk boundary. Per-round participation masks come from the
        same PRNG stream as the pipelined path, so the trajectories match.

        Without a state checkpointer the whole run is ONE dispatch, as
        before. With a snapshot-capable checkpointer the run dispatches in
        ``checkpoint_every``-round chunks and each boundary's host touch
        (the fused pull that already happens there) also snapshots the
        state trees for a durable, crash-consistent save — checkpointing
        no longer costs the fast path (``state_checkpointer`` is not in
        ``_chunk_ineligibility``). The scan body is identical for every
        chunk length, so a chunked-with-checkpoints run is bit-identical
        to the single-dispatch one (pinned by tests).

        With observability enabled the per-round gauges, JSONL ``round`` /
        ``telemetry`` events and reporter observability payloads are
        reconstructed from the stacked outputs — the SAME
        ``_record_round_metrics`` the pipelined consumer runs, so nothing
        is pipelined-only. The HealthWatchdog screens each round's
        telemetry in order; a halt raises ``TrainingHealthError`` naming
        the first offending round (the chunk's device work has already
        completed, but the failure is just as loud)."""
        if start_round > n_rounds:
            return  # restored state already covers the requested rounds
        sc = self.state_checkpointer
        chunk_ckpt = (sc is not None
                      and hasattr(sc, "save_simulation_snapshot"))
        self._fit_n_rounds = n_rounds
        with self._ckpt_writer_scope(chunk_ckpt) as writer:
            s = start_round
            while s <= n_rounds:
                k = self._rounds_per_dispatch(n_rounds, s)
                self._run_sync_chunk(s, k)
                if chunk_ckpt:
                    # the snapshot rides the chunk-boundary host touch: a
                    # host pull of the fresh state outputs BEFORE the next
                    # chunk's dispatch donates them away
                    trees = jax.device_get({
                        "server_state": self.server_state,
                        "client_states": self.client_states,
                    })
                    sc.save_simulation_snapshot(
                        trees, s + k - 1, self.n_clients,
                        list(self.history), writer=writer,
                        fleet=self._fleet_snapshot_doc(),
                    )
                s += k

    def _run_sync_chunk(self, start_round: int, k: int) -> None:
        """Dispatch rounds ``[start_round, start_round+k)`` as one compiled
        scan and run their host epilogue (the pre-checkpointing
        ``_fit_chunked`` body, offset-aware)."""
        obs = self.observability
        n_rounds = k
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            compiles_before = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        t_start = time.time()
        val_batches, val_counts = self._val_batches()
        test = self._test_batches()
        chunked = self._make_chunked_fit_with_eval()
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        rounds = range(start_round, start_round + k)
        plans = [self._round_plan(r) for r in rounds]
        idx = jnp.asarray(np.stack([p[0] for p in plans]))
        em = jnp.asarray(np.stack([p[1] for p in plans]))
        sm = jnp.asarray(np.stack([p[2] for p in plans]))
        mask_stack = jnp.stack([
            # the supervisor keep-mask is a pure function of (ledger,
            # round), so computing the whole chunk's masks ahead of the
            # dispatch sees the same values the per-round path would
            self._apply_recovery_keep(
                self.client_manager.sample(
                    jax.random.fold_in(self.rng, 2000 + r), r
                ),
                r,
            )
            for r in rounds
        ])
        masks_np = np.asarray(mask_stack)
        x_bank, y_bank = self._sharded_train_banks()
        args = [self.server_state, self.client_states,
                x_bank, y_bank, idx, em, sm,
                mask_stack, jnp.asarray(start_round, jnp.int32),
                val_batches, val_counts]
        if test is not None:
            args.extend(test)
        with obs.span("fit_chunk", cat="fit", rounds=n_rounds,
                      start_round=start_round) as chunk_span:
            self.server_state, self.client_states, outs = chunked(*args)
            # fence (enabled path only): total device wait for the chunk,
            # amortized per round below
            _, device_wait_total = obs.fence(outs)
            stacked = jax.device_get(outs)  # the chunk's ONE fused host pull
            if obs.enabled:
                chunk_span.set(device_wait_s=device_wait_total)
        compiles_after = compile_s_after = None
        if obs.enabled:
            compiles_after = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_after = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        per_round_s = (time.time() - t_start) / max(n_rounds, 1)
        device_wait_round = device_wait_total / max(n_rounds, 1)
        self._chunked_epilogue(
            n_rounds, stacked, masks_np, compiles_before, compile_s_before,
            compiles_after, compile_s_after, per_round_s, device_wait_round,
            start_round=start_round,
        )

    def _chunked_epilogue(
        self, n_rounds: int, stacked: dict, masks_np: np.ndarray,
        compiles_before: float, compile_s_before: float,
        compiles_after: float | None, compile_s_after: float | None,
        per_round_s: float, device_wait_round: float,
        async_plan=None, start_round: int = 1,
        cohort_infos=None, registry_ids=None,
    ) -> None:
        """Per-round host epilogue over a chunked dispatch's stacked
        outputs: failure screen, RoundRecords, metrics/reports, watchdog —
        shared by the synchronous chunked route, the buffered-async
        chunked route (``async_plan`` adds per-event staleness/cadence
        facts to each round's metrics) and the cohort chunked route
        (``cohort_infos``: per-round cohort summary dicts;
        ``registry_ids``: [R, K] slot->registry-id map so failures,
        fleet absorption and quarantine name REAL clients).
        ``start_round`` offsets the round numbering for non-initial
        chunks (checkpoint boundaries, resume)."""
        obs = self.observability
        telemetry_stack = stacked.get("telemetry")
        quarantine_stack = stacked.get("quarantine")
        for i in range(n_rounds):
            rnd = start_round + i
            per_fit_i = {
                k: v[i] for k, v in stacked["per_client_fit_losses"].items()
            }
            ids_i = (np.asarray(registry_ids[i])
                     if registry_ids is not None else None)
            # logs per-round failures; cannot terminate (eligibility
            # guarantees accept_failures=True on this path)
            failed = self.failure_policy.check(per_fit_i, masks_np[i])
            eval_losses = {
                k: float(v[i]) for k, v in stacked["eval_losses"].items()
            }
            eval_metrics = {
                k: float(v[i]) for k, v in stacked["eval_metrics"].items()
            }
            if "test_losses" in stacked:
                eval_losses.update({
                    f"test - {k}": float(v[i])
                    for k, v in stacked["test_losses"].items()
                })
                eval_metrics.update({
                    f"test - {k}": float(v[i])
                    for k, v in stacked["test_metrics"].items()
                })
            rec = RoundRecord(
                round=rnd,
                fit_losses={
                    k: float(v[i]) for k, v in stacked["fit_losses"].items()
                },
                fit_metrics={
                    k: float(v[i]) for k, v in stacked["fit_metrics"].items()
                },
                eval_losses=eval_losses,
                eval_metrics=eval_metrics,
                # one dispatch covers the whole run: report the amortized
                # per-round wall; there is no separable eval wall on-device
                fit_elapsed_s=per_round_s,
                eval_elapsed_s=0.0,
            )
            self.history.append(rec)
            telemetry_i = None
            if telemetry_stack is not None:
                telemetry_i = {
                    k: np.asarray(v[i])
                    for k, v in telemetry_stack.as_dict().items()
                }
            async_info_i = (self._async_event_info(async_plan, rnd - 1)
                            if async_plan is not None else None)
            # fleet-ledger absorb BEFORE the chunk boundary's snapshot
            # (taken after this epilogue returns) — the frame's ledger is
            # as-of the chunk's last round, matching the pipelined path
            fleet_info = self._fleet_absorb_round(
                rnd, masks_np[i], per_fit_i, telemetry_i,
                registry_ids=ids_i,
                quarantine_mask=(np.asarray(quarantine_stack[i])
                                 if quarantine_stack is not None else None),
                failed=failed,
                async_info=async_info_i,
            )
            obs_summary = None
            if obs.enabled:
                # the single dispatch's compiles/device time attribute to
                # round 1 / amortize per round — disclosed by execution_mode
                obs_summary = self._record_round_metrics(
                    rnd, rec, masks_np[i], per_fit_i, failed,
                    compiles_before, compile_s_before, device_wait_round,
                    compiles_after=(compiles_after if i == 0
                                    else compiles_before),
                    compile_s_after=(compile_s_after if i == 0
                                     else compile_s_before),
                    telemetry=telemetry_i,
                    async_info=async_info_i,
                    cohort_info=(cohort_infos[i]
                                 if cohort_infos is not None else None),
                    fleet_info=fleet_info,
                    registry_ids=ids_i,
                )
            if quarantine_stack is not None:
                self._emit_quarantine_metrics(
                    rnd, np.asarray(quarantine_stack[i]), ids=ids_i
                )
            for rep in self.reporters:
                payload = {
                    "fit_losses": rec.fit_losses,
                    "fit_metrics": rec.fit_metrics,
                    "eval_losses": rec.eval_losses,
                    "eval_metrics": rec.eval_metrics,
                    "fit_elapsed_s": rec.fit_elapsed_s,
                    "eval_elapsed_s": rec.eval_elapsed_s,
                    "execution_mode": EXEC_CHUNKED,
                }
                if obs_summary is not None:
                    payload["observability"] = dict(obs_summary)
                rep.report(payload, round=rnd)
            if telemetry_i is not None and obs.watchdog is not None:
                obs.watchdog.observe(
                    rnd, telemetry_i, masks_np[i],
                    rec.fit_losses.get("backward", float("nan")),
                    obs=obs, reporters=self.reporters,
                )
            # recovery probation (see _finish_round): healthy rounds only
            self._note_recovery_round(rnd)

    # -- cohort-slot path (server/registry.py) --------------------------
    def _count_cohort_roundtrip(self) -> None:
        """One host round-trip against the registry — a cohort draw +
        row gather/scatter + program dispatch paid on the host. The
        pipelined path pays one per ROUND; the chunked path one per
        R-round dispatch; async-over-registry one per buffer-fill event.
        ``fl_cohort_host_roundtrips_total`` is the measured side of the
        chunked path's O(rounds/R) host-barrier claim."""
        obs = self.observability
        if obs.enabled:
            obs.registry.counter(
                "fl_cohort_host_roundtrips_total",
                help="host round-trips paid against the client registry "
                     "(one per dispatch: cohort draw + gather/scatter)",
            ).inc()

    def _stage_cohort_round(self, rnd: int) -> dict:
        """One round's slot tensors, staged: sample the cohort ids from
        the dense path's exact PRNG stream (``fold_in(rng, 2000+round)``),
        assemble the ``[K, ...]`` host tensors from the registry, and
        ``device_put`` the big ones (sharded onto the clients axis under a
        mesh). Pure function of (rng, round, registry data) — safe to run
        on the prefetcher's worker thread, overlapping device execution;
        per-client STATE is deliberately absent (it has a read-after-write
        dependency on the previous round's scatter — see
        ``_run_cohort_round``)."""
        idx, valid = self.client_manager.sample_indices(
            jax.random.fold_in(self.rng, 2000 + rnd), rnd, self.n_clients
        )
        t0 = time.perf_counter()
        # the staging-overlap span: on the prefetch worker it runs INSIDE
        # the previous round's `round` span wall — visible overlap in the
        # trace timeline
        with self.observability.span("cohort_stage", round=rnd,
                                     valid=int(valid)) as sp:
            staged = self.registry.stage_round(
                idx, valid, self._base_entropy, rnd
            )
            b = self._program_builder
            cs = b.client_sharding()
            put = ((lambda t: b.put(t, cs)) if b.mesh is not None
                   else jax.device_put)
            staged["batches"] = put(staged["batches"])
            staged["val_batches"] = put(staged["val_batches"])
            staged["mask"] = jnp.asarray(staged["mask"])
            staged["sample_counts"] = jnp.asarray(staged["sample_counts"])
            staged["val_counts"] = jnp.asarray(staged["val_counts"])
            staged["stage_ms"] = (time.perf_counter() - t0) * 1e3
            sp.set(stage_ms=round(staged["stage_ms"], 3),
                   staged_bytes=staged["staged_bytes"])
        return staged

    def _await_registry_scatter(self) -> None:
        """Block until the consumer has scattered the PREVIOUS round's
        rows into the registry (the host-side read-after-write edge of the
        gather/scatter cycle), while still surfacing consumer failures —
        a raised epilogue must not leave the producer waiting forever."""
        ev = self._registry_scatter_event
        if ev is None:
            return
        consumer = self._consumer
        while not ev.wait(0.05):
            if consumer is not None:
                consumer.raise_pending()
        self._registry_scatter_event = None

    def _fit_cohort(self, n_rounds: int, start_round: int = 1) -> None:
        """fit()'s cohort-slot route: every round dispatches the SAME
        compiled [slots]-shaped fit/eval programs regardless of registry
        size. Per round the producer takes the prefetcher's staged slot
        data (staged during the previous round's device work), gathers the
        sampled clients' persistent rows from the host registry, runs
        fit+eval, and hands the results — including the updated rows — to
        the RoundConsumer, whose single fused device->host transfer also
        feeds the registry scatter."""
        obs = self.observability
        if start_round > n_rounds:
            return
        self._fit_n_rounds = n_rounds
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        self._registry_scatter_event = None
        with self._ckpt_writer_scope(
            bool(self.model_checkpointers
                 or self.state_checkpointer is not None),
            attach_model_ckpts=True,
        ):
            consumer = self._consumer = RoundConsumer(
                maxsize=self.pipeline_depth
            )
            prefetcher = self._prefetcher = RoundPrefetcher(self)
            try:
                prefetcher.schedule(start_round)
                for rnd in range(start_round, n_rounds + 1):
                    consumer.raise_pending()
                    with obs.maybe_profile(rnd):
                        self._run_cohort_round(rnd)
                consumer.flush()
            finally:
                consumer.close()
                prefetcher.close()
                # retained for the postmortem verdict: which round's host
                # epilogue last FINISHED before this run ended
                self._last_epilogue_round = consumer.last_completed_round
                self._consumer = None
                self._prefetcher = None
                self._registry_scatter_event = None

    def _run_cohort_round(self, rnd: int) -> None:
        """Producer half of one cohort-slot round: staged slot data in,
        registry state rows gathered and installed, fit+eval dispatched,
        epilogue (fused pull + registry scatter + records/reports)
        submitted to the consumer."""
        obs = self.observability
        consumer = self._consumer
        prefetcher = self._prefetcher
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            compiles_before = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        t0 = time.time()
        # per-round host boundary: admin retunes rebind server_state before
        # this round's programs read it (data staging has no dependency)
        self._apply_admin_retunes(rnd)
        with obs.span("round", round=rnd, kind="cohort"):
            with obs.span("configure_fit", round=rnd):
                staged = (prefetcher.take(rnd) if prefetcher is not None
                          else self._stage_cohort_round(rnd))
            if prefetcher is not None and rnd < self._fit_n_rounds:
                # round r+1's DATA staging overlaps round r's device work
                # (it has no state dependency); only the state gather below
                # waits for the previous scatter
                prefetcher.schedule(rnd + 1)
            self._await_registry_scatter()
            idx, valid = staged["idx"], staged["valid"]
            sup = self._recovery_supervisor
            if sup is not None:
                # supervisor quarantine in REGISTRY-id space: a sampled
                # slot whose id is on the roster is masked out (its row
                # still gathers/scatters — zero-weight, exactly like an
                # unsampled client); pass-through while idle
                drop = sup.quarantined_ids(rnd)
                if drop:
                    keep = (~np.isin(np.asarray(idx),
                                     np.asarray(drop))).astype(np.float32)
                    staged["mask"] = staged["mask"] * jnp.asarray(keep)
            with obs.span("cohort_gather", round=rnd,
                          valid=valid) as gather_span:
                g0 = time.perf_counter()
                b = self._program_builder
                client_rows = self.registry.gather_client_states(idx)
                if b.mesh is not None:
                    self.client_states = b.put(
                        client_rows, b.client_state_shardings(
                            self.client_states
                        )
                    )
                else:
                    self.client_states = jax.device_put(client_rows)
                srows = self.registry.gather_strategy_rows(idx)
                if srows is not None:
                    srows_dev = (b.put(srows, b.client_sharding())
                                 if b.mesh is not None
                                 else jax.device_put(srows))
                    self.server_state = self.strategy.scatter_state_rows(
                        self.server_state, srows_dev
                    )
                gather_ms = (time.perf_counter() - g0) * 1e3
                gather_span.set(gather_ms=gather_ms)
            telemetry = None
            fit_args = [
                self.server_state, self.client_states, staged["batches"],
                staged["mask"], jnp.asarray(rnd, jnp.int32),
                staged["val_batches"], staged["sample_counts"],
            ]
            with obs.span("fit_round", round=rnd) as fit_span:
                if self._telemetry_enabled:
                    (self.server_state, self.client_states, fit_losses,
                     fit_metrics, per_client_fit_losses,
                     telemetry) = self._fit_round_t(*fit_args)
                else:
                    (self.server_state, self.client_states, fit_losses,
                     fit_metrics,
                     per_client_fit_losses) = self._fit_round(*fit_args)
                _, device_wait_s = obs.fence(
                    (fit_losses, fit_metrics, per_client_fit_losses)
                )
                fit_span.set(device_wait_s=device_wait_s)
            need_pre = any(m == CheckpointMode.PRE_AGGREGATION
                           for m, _ in self.model_checkpointers)
            need_post = any(m == CheckpointMode.POST_AGGREGATION
                            for m, _ in self.model_checkpointers)
            pre_agg_params = None
            if need_pre:
                with obs.span("state_snapshot", round=rnd, what="pre_agg"):
                    pre_agg_params = jax.tree_util.tree_map(
                        jnp.copy, self.client_states.params
                    )
            t1 = time.time()
            with obs.span("eval_round", round=rnd) as eval_span:
                ev_args = (self.server_state, self.client_states,
                           staged["val_batches"], staged["val_counts"])
                if self._telemetry_enabled:
                    (self.client_states, eval_losses, eval_metrics, _pl,
                     _pm, ev_nonfinite) = self._eval_round_t(*ev_args)
                    telemetry = telemetry.replace(
                        nonfinite_eval_loss=ev_nonfinite
                    )
                else:
                    (self.client_states, eval_losses, eval_metrics, _pl,
                     _pm) = self._eval_round(*ev_args)
                _, eval_wait = obs.fence((eval_losses, eval_metrics))
                device_wait_s += eval_wait
                eval_span.set(device_wait_s=eval_wait)
            post_agg_params = None
            state_trees = None
            snapshot_state = (
                self.state_checkpointer is not None
                and self._checkpoint_due(rnd)
            )
            if need_post or snapshot_state:
                with obs.span("state_snapshot", round=rnd, what="post_agg"):
                    if need_post:
                        post_agg_params = jax.tree_util.tree_map(
                            jnp.copy, self.global_params
                        )
                    if snapshot_state:
                        state_trees = jax.tree_util.tree_map(
                            jnp.copy,
                            {"server_state": self.server_state,
                             "client_states": self.client_states},
                        )
            t2 = time.time()
            compiles_after = compile_s_after = None
            if obs.enabled:
                compiles_after = obs.registry.counter(
                    "jax_backend_compiles_total").value
                compile_s_after = obs.registry.counter(
                    "jax_backend_compiles_seconds_total").value
            device_results = {
                "mask": staged["mask"],
                "fit_losses": fit_losses,
                "fit_metrics": fit_metrics,
                "per_client_fit_losses": per_client_fit_losses,
                "eval_losses": eval_losses,
                "eval_metrics": eval_metrics,
                # the updated persistent rows ride the consumer's fused
                # transfer; no copies needed — the producer's scatter gate
                # keeps these buffers alive until the pull completes
                "_registry_rows": {
                    "client_states": self.client_states,
                    "strategy_rows": self.strategy.state_rows(
                        self.server_state
                    ),
                },
            }
            if telemetry is not None:
                device_results["telemetry"] = telemetry
            q_fn = getattr(self.strategy, "quarantine_mask", None)
            if q_fn is not None and obs.enabled:
                device_results["_quarantine"] = jnp.copy(
                    q_fn(self.server_state)
                )
            if pre_agg_params is not None:
                device_results["_pre_agg_params"] = pre_agg_params
            if post_agg_params is not None:
                device_results["_post_agg_params"] = post_agg_params
            if state_trees is not None:
                device_results["_state_trees"] = state_trees
            scatter_event = threading.Event()
            self._registry_scatter_event = scatter_event
            work = _RoundWork(
                round=rnd,
                device_results=device_results,
                fit_elapsed_s=t1 - t0,
                eval_elapsed_s=t2 - t1,
                device_wait_s=device_wait_s,
                compiles_before=compiles_before,
                compile_s_before=compile_s_before,
                compiles_after=compiles_after,
                compile_s_after=compile_s_after,
                cohort_meta={
                    "idx": idx, "valid": valid,
                    "slots": self.n_clients,
                    "registry_size": self.registry_size,
                    "stage_ms": staged["stage_ms"],
                    "gather_ms": gather_ms,
                    "staged_bytes": staged["staged_bytes"],
                    "scatter_event": scatter_event,
                    "rounds_per_dispatch": 1,
                    "cohort_draw": "host",
                },
            )
            self._count_cohort_roundtrip()
            if consumer is not None:
                consumer.submit_round(
                    rnd, functools.partial(self._finish_round, work))
                if not self.failure_policy.accept_failures:
                    consumer.flush()
            else:
                self._finish_round(work)

    # -- cohort chunked route (in-graph draw + window exchange) ---------
    def _make_cohort_chunk(self):
        """Compile the cohort chunked scan: R federated rounds per
        dispatch over the virtualized registry, with ZERO host touches
        between rounds. Each scan step (1) draws the round's cohort ids
        IN-GRAPH via the manager's ``draw_cohort`` — a pure function of
        ``fold_in(seed, 2000+round)``, bit-identical to the host sampler
        the pipelined path runs — (2) resolves the ids against the
        device-staged registry WINDOW (``searchsorted`` over the sorted
        window ids; pad slots repeat a real id, so every slot gathers a
        real row), (3) runs the exact slot ``fit_round``/``eval_round``
        sequence of one pipelined cohort round, and (4) scatters the
        post-eval rows (client states + strategy rows) back into the
        window (pad destinations drop). The window is the chunk's
        double-buffered stand-in for the host registry: rows enter it
        once per chunk and leave once per chunk, so host round-trips
        shrink from O(rounds) to O(rounds/R).

        The scan outputs carry each round's drawn ids/valid count so the
        driver can assert in-graph/host draw parity at the pull — the
        window was built from the HOST mirror's draws, and any divergence
        would silently corrupt the exchange."""
        if self._cohort_chunk_jit is not None:
            return self._cohort_chunk_jit
        telemetry_on = self._telemetry_enabled
        fit_round = (self._fit_round_fn_t if telemetry_on
                     else self._fit_round_fn)
        eval_round = (self._eval_round_fn_t if telemetry_on
                      else self._eval_round_fn)
        quarantine_fn = (getattr(self.strategy, "quarantine_mask", None)
                         if self.observability.enabled else None)
        strategy = self.strategy
        draw = self.client_manager.draw_cohort
        slots = self.n_clients
        has_srows = self.registry.has_strategy_rows

        def chunk(server_state, client_states, w_client, w_srows,
                  base_rng, window_ids, batches, masks, sample_counts,
                  val_batches, val_counts, start_round):
            w = window_ids.shape[0]

            def body(carry, per_round):
                server_state, client_states, w_client, w_srows, r = carry
                batches_r, mask_r, sc_r, vb_r, vc_r = per_round
                ids, valid = draw(
                    jax.random.fold_in(base_rng, 2000 + r), r, slots
                )
                with stage_attr.stage("cohort_exchange"):
                    pos = jnp.searchsorted(window_ids, ids).astype(jnp.int32)
                    client_states = jax.tree_util.tree_map(
                        lambda t: t[pos], w_client
                    )
                    if has_srows:
                        server_state = strategy.scatter_state_rows(
                            server_state,
                            jax.tree_util.tree_map(
                                lambda t: t[pos], w_srows
                            ),
                        )
                fit_outs = fit_round(
                    server_state, client_states, batches_r, mask_r, r,
                    vb_r, sc_r,
                )
                round_telemetry = None
                if telemetry_on:
                    (server_state, client_states, fit_losses, fit_metrics,
                     per_fit, round_telemetry) = fit_outs
                else:
                    (server_state, client_states, fit_losses, fit_metrics,
                     per_fit) = fit_outs
                ev_outs = eval_round(
                    server_state, client_states, vb_r, vc_r
                )
                if telemetry_on:
                    (client_states, ev_losses, ev_metrics, _pl, _pm,
                     ev_nonfinite) = ev_outs
                    round_telemetry = round_telemetry.replace(
                        nonfinite_eval_loss=ev_nonfinite
                    )
                else:
                    client_states, ev_losses, ev_metrics, _pl, _pm = ev_outs
                out = {
                    "fit_losses": fit_losses,
                    "fit_metrics": fit_metrics,
                    "per_client_fit_losses": per_fit,
                    "eval_losses": ev_losses,
                    "eval_metrics": ev_metrics,
                    "cohort_ids": ids,
                    "cohort_valid": valid,
                }
                if round_telemetry is not None:
                    out["telemetry"] = round_telemetry
                if quarantine_fn is not None:
                    out["quarantine"] = quarantine_fn(server_state)
                # write-back: post-eval rows land at their window position;
                # pad slots (>= valid) target index w — dropped, exactly
                # like an unsampled client on the pipelined path
                with stage_attr.stage("cohort_exchange"):
                    dest = jnp.where(
                        jnp.arange(slots, dtype=jnp.int32) < valid, pos, w
                    )
                    w_client = jax.tree_util.tree_map(
                        lambda wt, c: wt.at[dest].set(c, mode="drop"),
                        w_client, client_states,
                    )
                    if has_srows:
                        w_srows = jax.tree_util.tree_map(
                            lambda wt, c: wt.at[dest].set(c, mode="drop"),
                            w_srows, strategy.state_rows(server_state),
                        )
                return (server_state, client_states, w_client, w_srows,
                        r + 1), out

            (server_state, client_states, w_client, w_srows, _), outs = (
                jax.lax.scan(
                    body,
                    (server_state, client_states, w_client, w_srows,
                     start_round),
                    (batches, masks, sample_counts, val_batches,
                     val_counts),
                )
            )
            return server_state, client_states, w_client, w_srows, outs

        # donate the carried states AND the window trees: the caller
        # replaces all four with the scan outputs, so XLA updates the
        # large [W, ...] window buffers in place (mesh never reaches this
        # path — mesh+cohort demotes to pipelined)
        self._cohort_chunk_jit = self._program_builder.jit(
            chunk, donate=(0, 1, 2, 3)
        )
        return self._cohort_chunk_jit

    def _stage_cohort_chunk(self, start_round: int, k: int) -> dict:
        """One chunk's host staging: sample rounds ``[start_round,
        start_round+k)`` from the dense path's exact PRNG stream (the HOST
        mirror of the in-graph draw — it also fails fast on sampler
        overflow, before any device work), stack their slot tensors, build
        the chunk window and ``device_put`` the lot. Pure function of
        (rng, rounds, registry data) — safe on the prefetcher's worker
        thread, overlapping the previous chunk's device work. Window
        STATE rows are absent here (read-after-write on the previous
        chunk's scatter — the driver gathers them)."""
        draws = []
        for i in range(k):
            r = start_round + i
            idx, valid = self.client_manager.sample_indices(
                jax.random.fold_in(self.rng, 2000 + r), r, self.n_clients
            )
            draws.append((np.asarray(idx), int(valid)))
        t0 = time.perf_counter()
        with self.observability.span(
            "cohort_stage_chunk", start_round=start_round, rounds=k
        ) as sp:
            staged = self.registry.stage_chunk(
                draws, self._base_entropy, start_round
            )
            window_ids, w_real = self.registry.chunk_window(
                [d[0] for d in draws], [d[1] for d in draws],
                self.n_clients, k,
            )
            staged["window_ids"] = window_ids
            staged["w_real"] = w_real
            staged["mask_np"] = staged["mask"]
            staged["batches"] = jax.device_put(staged["batches"])
            staged["val_batches"] = jax.device_put(staged["val_batches"])
            staged["mask"] = jnp.asarray(staged["mask"])
            staged["sample_counts"] = jnp.asarray(staged["sample_counts"])
            staged["val_counts"] = jnp.asarray(staged["val_counts"])
            # int32 on device: draw_cohort ids are int32, and searchsorted
            # wants one dtype on both sides
            staged["window_ids_dev"] = jnp.asarray(
                window_ids.astype(np.int32)
            )
            staged["stage_ms"] = (time.perf_counter() - t0) * 1e3
            sp.set(stage_ms=round(staged["stage_ms"], 3),
                   staged_bytes=staged["staged_bytes"],
                   window=len(window_ids), window_real=w_real)
        return staged

    def _fit_cohort_chunked(self, n_rounds: int, start_round: int = 1
                            ) -> None:
        """fit()'s cohort chunked route: ``checkpoint_every``-round (or
        whole-run) chunks dispatch over the registry window while the
        prefetcher stages the NEXT chunk's draws + slot tensors behind the
        device work. Chunk boundaries keep the PR 12 semantics: the window
        rows scatter back into the registry first, then the cohort
        snapshot (slot states + registry dirty rows) persists exactly as
        the pipelined consumer would have written it."""
        obs = self.observability
        if start_round > n_rounds:
            return
        sc = self.state_checkpointer
        chunk_ckpt = sc is not None
        self._fit_n_rounds = n_rounds
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        prefetcher = self._prefetcher = RoundPrefetcher(self)
        try:
            with self._ckpt_writer_scope(chunk_ckpt) as writer:
                s = start_round
                prefetcher.schedule_chunk(
                    s, self._rounds_per_dispatch(n_rounds, s)
                )
                while s <= n_rounds:
                    k = self._rounds_per_dispatch(n_rounds, s)
                    staged = prefetcher.take_chunk(s, k)
                    if s + k <= n_rounds:
                        # chunk c+1's draws/staging overlap chunk c's
                        # device work; only the window ROW gather waits
                        # for c's boundary scatter (in _run_cohort_chunk)
                        prefetcher.schedule_chunk(
                            s + k,
                            self._rounds_per_dispatch(n_rounds, s + k),
                        )
                    with obs.span("cohort_chunk", start_round=s, rounds=k):
                        self._run_cohort_chunk(s, k, staged)
                    if chunk_ckpt:
                        trees = jax.device_get({
                            "server_state": self.server_state,
                            "client_states": self.client_states,
                        })
                        sc.save_cohort_snapshot(
                            trees, s + k - 1, self.n_clients,
                            self.registry_size, self.registry.export_rows(),
                            list(self.history), writer=writer,
                            fleet=self._fleet_snapshot_doc(),
                        )
                    s += k
        finally:
            prefetcher.close()
            self._prefetcher = None

    def _run_cohort_chunk(self, start_round: int, k: int,
                          staged: dict) -> None:
        """Dispatch one cohort chunk and run its host epilogue: window
        row gather (after the previous chunk's scatter — same-thread, so
        the ordering is structural), ONE compiled scan over k rounds, the
        in-graph/host draw-parity check, the boundary scatter back into
        the registry, then the shared chunked epilogue with per-round
        cohort facts."""
        obs = self.observability
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            compiles_before = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        t_start = time.time()
        chunked = self._make_cohort_chunk()
        with obs.span("cohort_gather", start_round=start_round,
                      window=int(staged["w_real"])) as gather_span:
            g0 = time.perf_counter()
            w_client_h, w_srows_h = self.registry.gather_window(
                staged["window_ids"]
            )
            w_client = jax.device_put(w_client_h)
            w_srows = (jax.device_put(w_srows_h)
                       if w_srows_h is not None else {})
            gather_ms = (time.perf_counter() - g0) * 1e3
            gather_span.set(gather_ms=gather_ms)
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        args = [self.server_state, self.client_states, w_client, w_srows,
                self.rng, staged["window_ids_dev"], staged["batches"],
                staged["mask"], staged["sample_counts"],
                staged["val_batches"], staged["val_counts"],
                jnp.asarray(start_round, jnp.int32)]
        with obs.span("fit_cohort_chunk", cat="fit", rounds=k,
                      start_round=start_round) as chunk_span:
            (self.server_state, self.client_states, w_client, w_srows,
             outs) = chunked(*args)
            _, device_wait_total = obs.fence(
                (outs["fit_losses"], outs["eval_losses"])
            )
            stacked = jax.device_get(outs)  # the chunk's ONE fused pull
            rows_back = jax.device_get((w_client, w_srows))
            if obs.enabled:
                chunk_span.set(device_wait_s=device_wait_total)
        self._count_cohort_roundtrip()
        # in-graph/host draw parity: the window was built from the host
        # mirror's draws; a divergent in-graph draw would gather/scatter
        # the WRONG rows — fail loudly, never train through it
        ids_host = np.asarray(staged["idx"])
        valid_host = np.asarray(staged["valid"], np.int64)
        ids_dev = np.asarray(stacked.pop("cohort_ids"), np.int64)
        valid_dev = np.asarray(stacked.pop("cohort_valid"), np.int64)
        if not (np.array_equal(ids_dev, np.asarray(ids_host, np.int64))
                and np.array_equal(valid_dev, valid_host)):
            raise RuntimeError(
                "in-graph cohort draw diverged from the host sampler for "
                f"rounds [{start_round}, {start_round + k}): the "
                f"{type(self.client_manager).__name__}.draw_cohort "
                "contract (bit-identical to sample_indices) is broken — "
                "the chunk's window exchange cannot be trusted"
            )
        with obs.span("registry_scatter", start_round=start_round,
                      valid=int(staged["w_real"])) as sc_span:
            s0 = time.perf_counter()
            wc_back, ws_back = rows_back
            self.registry.scatter(
                staged["window_ids"], int(staged["w_real"]), wc_back,
                ws_back if w_srows_h is not None else None,
            )
            scatter_ms = (time.perf_counter() - s0) * 1e3
            sc_span.set(scatter_ms=scatter_ms)
        compiles_after = compile_s_after = None
        if obs.enabled:
            compiles_after = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_after = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        per_round_s = (time.time() - t_start) / max(k, 1)
        device_wait_round = device_wait_total / max(k, 1)
        # per-round cohort facts: walls amortize over the chunk; the
        # rounds_per_dispatch/cohort_draw pair is what the perf report's
        # host-barrier columns read
        cohort_infos = [
            {
                "cohort_slots": self.n_clients,
                "cohort_valid": int(valid_host[i]),
                "registry_size": self.registry_size,
                "registry_dirty_rows": self.registry.dirty_rows,
                "stage_ms": round(staged["stage_ms"] / k, 3),
                "gather_ms": round(gather_ms / k, 3),
                "scatter_ms": round(scatter_ms / k, 3),
                "staged_bytes": int(staged["staged_bytes"] // k),
                "rounds_per_dispatch": k,
                "cohort_draw": "in_graph",
            }
            for i in range(k)
        ]
        self._chunked_epilogue(
            k, stacked, np.asarray(staged["mask_np"]),
            compiles_before, compile_s_before, compiles_after,
            compile_s_after, per_round_s, device_wait_round,
            start_round=start_round,
            cohort_infos=cohort_infos, registry_ids=ids_host,
        )

    # -- buffered-async path (server/async_schedule.py) -----------------
    @staticmethod
    def _async_event_info(plan, i: int) -> dict:
        """One event's host facts for the round record, plus the raw
        per-update staleness row (popped by ``_record_round_metrics``
        into the staleness histogram)."""
        info = plan.summarize_event(i)
        arr = plan.arrivals[i] > 0
        info["_staleness_values"] = [
            float(s) for s in plan.staleness[i][arr]
        ]
        return info

    def _fit_async(self, n_rounds: int, mode: str, plan,
                   start_event: int = 1) -> None:
        """fit()'s buffered-async route: the virtual-clock arrival
        schedule was resolved to a static event plan at fit() entry (pure
        function of the async config's seed, the FaultPlan and the cohort
        — identical across execution modes, resumes and processes); run
        the remaining buffer-fill EVENTS as compiled programs. Each event
        is one RoundRecord: cadence is set by arrival rate, not the tail.
        ``start_event`` > 1 continues a restored run whose pending buffer,
        event cursor and plan-prefix fingerprint ``_maybe_resume``
        verified."""
        obs = self.observability
        if obs.enabled:
            obs.log_event(
                "async_plan", events=n_rounds,
                buffer_size=self.async_config.buffer_size,
                staleness_mean=float(
                    plan.staleness[plan.arrivals > 0].mean()
                ) if n_rounds else 0.0,
                virtual_wall_s=float(plan.event_times[-1]),
                mean_cadence_vs=float(plan.cadences().mean()),
            )
        if start_event > n_rounds:
            return  # restored state already covers the requested events
        self._async_prefix_fps = None
        if self._ckpt_every() is not None:
            from fl4health_tpu.server.async_schedule import (
                plan_prefix_fingerprints,
            )

            self._async_prefix_fps = plan_prefix_fingerprints(plan)
        if self._cohort_active:
            # FedBuff over the registry: per-event occupancy swaps are
            # host work, so this composition is pipelined-only (the
            # chunked route demotes at _chunk_ineligibility)
            self._fit_async_registry(n_rounds, plan, start_event)
        elif mode == EXEC_CHUNKED:
            self._fit_async_chunked(n_rounds, plan, start_event)
        else:
            self._fit_async_pipelined(n_rounds, plan, start_event)

    def _staleness_exponent_input(self) -> jax.Array:
        """The staleness exponent as a traced PROGRAM INPUT, read from the
        live (outermost FedBuff) strategy attribute at each dispatch — so a
        rebind of ``strategy.staleness_exponent`` (the sweep engine's
        scalar hoisting) reaches the compiled async programs with zero
        recompiles. Falls back to 0.0 for exotic async strategies without
        the attribute (a legacy 2-arg ``async_aggregation_mask`` never
        receives it — ``_build_async_fns`` shims the call arity)."""
        return jnp.asarray(
            float(getattr(self.strategy, "staleness_exponent", 0.0)),
            jnp.float32,
        )

    def _stage_prologue_batches(self):
        """Data-plan-1 batches for the async prologue, staged with the
        builder's clients sharding (no-op unsharded)."""
        return self._program_builder.put(
            self._round_batches(1), self._program_builder.client_sharding()
        )

    def _fit_async_pipelined(self, n_rounds: int, plan,
                             start_event: int = 1) -> None:
        """Per-event async path: prologue dispatch fills the pending
        buffer, then each buffer-fill event dispatches one fused
        aggregate->eval->restart program while the RoundConsumer runs the
        previous event's host epilogue and the prefetcher stages the next
        event's restart batches (data plan e+1). On resume
        (``start_event`` > 1) the restored pending buffer replaces the
        prologue — the interrupted run's in-flight updates pick up
        mid-plan."""
        obs = self.observability
        prologue_jit, _ = self._make_async_programs()
        with obs.span("setup", cat="fit"):
            val_batches, val_counts = self._val_batches()
        self._fit_n_rounds = n_rounds
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        with self._ckpt_writer_scope(self._ckpt_every() is not None):
            consumer = self._consumer = RoundConsumer(
                maxsize=self.pipeline_depth
            )
            prefetcher = self._prefetcher = RoundPrefetcher(self)
            try:
                if start_event == 1:
                    with obs.span("async_prologue", cat="fit"):
                        batches1 = self._stage_prologue_batches()
                        (self.client_states,
                         self._async_pending) = prologue_jit(
                            self.server_state, self.client_states, batches1,
                            val_batches,
                        )
                # event e restarts its clients on data plan e+1
                prefetcher.schedule(start_event + 1)
                for e in range(start_event, n_rounds + 1):
                    consumer.raise_pending()
                    with obs.maybe_profile(e):
                        self._run_async_event(e, plan, val_batches,
                                              val_counts)
                consumer.flush()
            finally:
                consumer.close()
                prefetcher.close()
                # retained for the postmortem verdict: which round's host
                # epilogue last FINISHED before this run ended
                self._last_epilogue_round = consumer.last_completed_round
                self._consumer = None
                self._prefetcher = None
                self._async_pending = None

    def _run_async_event(self, e: int, plan, val_batches, val_counts) -> None:
        """Producer half of one buffer-fill event (mirrors ``_run_round``):
        one fused dispatch consumes the event's arrivals, evaluates the
        fresh global and restarts the consumed clients; the host epilogue
        (failure screen, records, metrics, reports, watchdog) runs on the
        RoundConsumer thread."""
        obs = self.observability
        consumer = self._consumer
        prefetcher = self._prefetcher
        _, event_jit = self._make_async_programs()
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            compiles_before = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        t0 = time.time()
        # per-event host boundary: state-kind retunes rebind server_state;
        # a staleness_exponent setattr lands via the live dispatch input
        # (_staleness_exponent_input) this very event
        self._apply_admin_retunes(e)
        with obs.span("round", round=e, kind="async_event"):
            arrivals = jnp.asarray(plan.arrivals[e - 1])
            staleness = jnp.asarray(plan.staleness[e - 1])
            batches_next = (prefetcher.take(e + 1) if prefetcher is not None
                            else self._round_batches(e + 1))
            if prefetcher is not None and e < self._fit_n_rounds:
                prefetcher.schedule(e + 2)
            args = [self.server_state, self.client_states,
                    self._async_pending, batches_next, arrivals, staleness,
                    jnp.asarray(e, jnp.int32), val_batches, val_counts,
                    self._staleness_exponent_input()]
            test = self._test_batches()
            if test is not None:
                args.extend(test)
            with obs.span("async_event", round=e) as ev_span:
                (self.server_state, self.client_states, self._async_pending,
                 out) = event_jit(*args)
                _, device_wait_s = obs.fence(
                    (out["fit_losses"], out["eval_losses"])
                )
                ev_span.set(device_wait_s=device_wait_s)
            compiles_after = compile_s_after = None
            if obs.enabled:
                compiles_after = obs.registry.counter(
                    "jax_backend_compiles_total").value
                compile_s_after = obs.registry.counter(
                    "jax_backend_compiles_seconds_total").value
            device_results = {
                "mask": plan.arrivals[e - 1],
                "fit_losses": out["fit_losses"],
                "fit_metrics": out["fit_metrics"],
                "per_client_fit_losses": out["per_client_fit_losses"],
                "eval_losses": out["eval_losses"],
                "eval_metrics": out["eval_metrics"],
            }
            if "telemetry" in out:
                device_results["telemetry"] = out["telemetry"]
            if "quarantine" in out:
                device_results["_quarantine"] = out["quarantine"]
            if "test_losses" in out:
                device_results["test_losses"] = out["test_losses"]
                device_results["test_metrics"] = out["test_metrics"]
            resume_meta = None
            if self._checkpoint_due(e):
                # async snapshot: server + client stack + the in-flight
                # pending buffer — device-side copies (all three are
                # donated into the next event) riding the consumer's
                # fused transfer, with the plan-prefix fingerprint and
                # virtual clock the resume verifies
                with obs.span("state_snapshot", round=e, what="async"):
                    device_results["_state_trees"] = jax.tree_util.tree_map(
                        jnp.copy,
                        {"server_state": self.server_state,
                         "client_states": self.client_states,
                         "pending": self._async_pending},
                    )
                resume_meta = {
                    "plan_fingerprint": self._async_prefix_fps[e - 1],
                    "virtual_time_s": float(plan.event_times[e - 1]),
                }
            work = _RoundWork(
                round=e,
                device_results=device_results,
                fit_elapsed_s=time.time() - t0,
                eval_elapsed_s=0.0,  # eval is fused into the event program
                device_wait_s=device_wait_s,
                compiles_before=compiles_before,
                compile_s_before=compile_s_before,
                compiles_after=compiles_after,
                compile_s_after=compile_s_after,
                async_info=self._async_event_info(plan, e - 1),
                resume_meta=resume_meta,
            )
            if consumer is not None:
                consumer.submit_round(
                    e, functools.partial(self._finish_round, work))
                if not self.failure_policy.accept_failures:
                    # the failure screen must be able to terminate BEFORE
                    # the next event mutates state — same rule as sync
                    consumer.flush()
            else:
                self._finish_round(work)

    def _fit_async_chunked(self, n_rounds: int, plan,
                           start_event: int = 1) -> None:
        """Async chunked route: prologue dispatch + lax.scan dispatches
        over the buffer-fill events, then the shared chunked epilogue
        reconstructs per-event records (with staleness/cadence facts) from
        each stacked pull. Like the sync chunked route, an attached
        snapshot checkpointer splits the scan at ``checkpoint_every``
        boundaries and persists (server, clients, pending) there; on
        resume the restored pending buffer replaces the prologue."""
        obs = self.observability
        sc = self.state_checkpointer
        chunk_ckpt = self._ckpt_every() is not None
        self._fit_n_rounds = n_rounds
        val_batches, val_counts = self._val_batches()
        test = self._test_batches()
        prologue_jit, _ = self._make_async_programs()
        chunked = self._make_async_chunked()
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        if start_event == 1:
            with obs.span("async_prologue", cat="fit"):
                batches1 = self._stage_prologue_batches()
                self.client_states, pending = prologue_jit(
                    self.server_state, self.client_states, batches1,
                    val_batches,
                )
        else:
            pending = self._async_pending  # restored mid-plan buffer
        # the attribute's job is done (the local carries the buffer from
        # here); clear it so no stale device tree outlives this fit()
        self._async_pending = None
        x_bank, y_bank = self._sharded_train_banks()
        with self._ckpt_writer_scope(chunk_ckpt) as writer:
            s = start_event
            while s <= n_rounds:
                k = self._rounds_per_dispatch(n_rounds, s)
                compiles_before = compile_s_before = 0.0
                if obs.enabled:
                    compiles_before = obs.registry.counter(
                        "jax_backend_compiles_total").value
                    compile_s_before = obs.registry.counter(
                        "jax_backend_compiles_seconds_total").value
                t_start = time.time()
                # event e restarts on data plan e+1: stack plans s+1..s+k
                plans = [self._round_plan(e + 1)
                         for e in range(s, s + k)]
                idx = jnp.asarray(np.stack([p[0] for p in plans]))
                em = jnp.asarray(np.stack([p[1] for p in plans]))
                sm = jnp.asarray(np.stack([p[2] for p in plans]))
                args = [self.server_state, self.client_states, pending,
                        x_bank, y_bank, idx, em, sm,
                        jnp.asarray(plan.arrivals[s - 1:s - 1 + k]),
                        jnp.asarray(plan.staleness[s - 1:s - 1 + k]),
                        jnp.asarray(s, jnp.int32),
                        val_batches, val_counts,
                        self._staleness_exponent_input()]
                if test is not None:
                    args.extend(test)
                with obs.span("fit_async_chunk", cat="fit", rounds=k,
                              start_event=s) as chunk_span:
                    (self.server_state, self.client_states, pending,
                     outs) = chunked(*args)
                    _, device_wait_total = obs.fence(outs)
                    stacked = jax.device_get(outs)
                    if obs.enabled:
                        chunk_span.set(device_wait_s=device_wait_total)
                compiles_after = compile_s_after = None
                if obs.enabled:
                    compiles_after = obs.registry.counter(
                        "jax_backend_compiles_total").value
                    compile_s_after = obs.registry.counter(
                        "jax_backend_compiles_seconds_total").value
                per_round_s = (time.time() - t_start) / max(k, 1)
                device_wait_round = device_wait_total / max(k, 1)
                self._chunked_epilogue(
                    k, stacked, plan.arrivals[s - 1:s - 1 + k],
                    compiles_before, compile_s_before, compiles_after,
                    compile_s_after, per_round_s, device_wait_round,
                    async_plan=plan, start_round=s,
                )
                if chunk_ckpt:
                    e_done = s + k - 1
                    trees = jax.device_get({
                        "server_state": self.server_state,
                        "client_states": self.client_states,
                        "pending": pending,
                    })
                    sc.save_async_snapshot(
                        trees, e_done, self.n_clients, list(self.history),
                        plan_fingerprint=self._async_prefix_fps[e_done - 1],
                        virtual_time_s=float(plan.event_times[e_done - 1]),
                        writer=writer,
                        fleet=self._fleet_snapshot_doc(),
                    )
                s += k

    # -- buffered-async over the registry (FedBuff x cohort slots) -------
    def _fit_async_registry(self, n_rounds: int, plan,
                            start_event: int = 1) -> None:
        """FedBuff over the virtualized registry: the K buffer slots are
        SEATS, and the static :class:`RegistryEventPlan` decides which
        registry client occupies each seat at every buffer-fill event.
        When event *e* consumes a seat's update, the evicted occupant's
        persistent row scatters back into the host registry and the
        incoming occupant's row gathers in — O(K) host work per event, so
        the compiled event program never sees the registry size. The
        occupants' sample counts ride the pending buffer with their
        packets (``_build_async_fns``), so aggregation always weights a
        packet by the counts it TRAINED under, even after its seat was
        reassigned.

        Degenerate parity case (pinned by tests): ``K == N`` with
        FullParticipation seats every client forever — the plan's swaps
        are identities, the staged data plans match the dense ones, and
        the run is bit-identical to dense buffered-async fit()."""
        obs = self.observability
        prologue_jit, _ = self._make_async_programs()
        slots = self.n_clients
        self._fit_n_rounds = n_rounds
        self.server_state, self.client_states = _dedupe_donated(
            self.server_state, self.client_states
        )
        occ = np.asarray(plan.slot_ids[start_event - 1])
        with obs.span("cohort_gather", round=0, valid=slots):
            # seat the initial occupancy: persistent rows in
            self.client_states = jax.device_put(
                self.registry.gather_client_states(occ)
            )
            if self.registry.has_strategy_rows:
                self.server_state = self.strategy.scatter_state_rows(
                    self.server_state,
                    jax.device_put(self.registry.gather_strategy_rows(occ)),
                )
        with self._ckpt_writer_scope(
            bool(self.model_checkpointers), attach_model_ckpts=True,
        ):
            consumer = self._consumer = RoundConsumer(
                maxsize=self.pipeline_depth
            )
            try:
                # the prologue trains every seat's occupant on data plan 1
                with obs.span("async_prologue", cat="fit"):
                    staged = self.registry.stage_round(
                        occ, slots, self._base_entropy, 1
                    )
                    (self.client_states,
                     self._async_pending) = prologue_jit(
                        self.server_state, self.client_states,
                        jax.device_put(staged["batches"]),
                        jax.device_put(staged["val_batches"]),
                        jnp.asarray(staged["sample_counts"]),
                    )
                self._count_cohort_roundtrip()
                for e in range(start_event, n_rounds + 1):
                    consumer.raise_pending()
                    with obs.maybe_profile(e):
                        occ = self._run_async_registry_event(e, plan, occ)
                consumer.flush()
                # end of plan: every seat's live row persists — the
                # registry is the durable store, seats are transient
                rows = jax.device_get(self.client_states)
                srows = None
                if self.registry.has_strategy_rows:
                    srows = jax.device_get(
                        self.strategy.state_rows(self.server_state)
                    )
                self.registry.scatter(occ, slots, rows, srows)
            finally:
                consumer.close()
                self._last_epilogue_round = consumer.last_completed_round
                self._consumer = None
                self._async_pending = None

    def _run_async_registry_event(self, e: int, plan,
                                  occ_prev: np.ndarray) -> np.ndarray:
        """Producer half of one buffer-fill event over the registry:
        swap the consumed seats' occupants (scatter evicted rows, gather
        incoming rows), stage the restart wave's data for the new
        occupancy, dispatch the fused consume->eval->restart program, and
        hand the epilogue to the consumer with the PRE-swap occupancy —
        the consumed packets belong to the evicted occupants. Returns the
        post-swap occupancy for the next event."""
        obs = self.observability
        consumer = self._consumer
        _, event_jit = self._make_async_programs()
        slots = self.n_clients
        compiles_before = compile_s_before = 0.0
        if obs.enabled:
            compiles_before = obs.registry.counter(
                "jax_backend_compiles_total").value
            compile_s_before = obs.registry.counter(
                "jax_backend_compiles_seconds_total").value
        t0 = time.time()
        # same per-event admin boundary as the dense async path
        self._apply_admin_retunes(e)
        with obs.span("round", round=e, kind="async_event"):
            occ_next = np.asarray(plan.slot_ids[e])
            changed = np.nonzero(occ_prev != occ_next)[0]
            gather_ms = scatter_ms = 0.0
            if changed.size:
                with obs.span("registry_swap", round=e,
                              swapped=int(changed.size)) as swap_span:
                    s0 = time.perf_counter()
                    ch = jnp.asarray(changed)
                    has_srows = self.registry.has_strategy_rows
                    # evict: the consumed seats' occupants persist their
                    # rows under their OLD registry ids
                    out_rows = jax.device_get(jax.tree_util.tree_map(
                        lambda t: t[ch], self.client_states
                    ))
                    out_srows = None
                    srows_live = (self.strategy.state_rows(self.server_state)
                                  if has_srows else None)
                    if has_srows:
                        out_srows = jax.device_get(jax.tree_util.tree_map(
                            lambda t: t[ch], srows_live
                        ))
                    self.registry.scatter(
                        occ_prev[changed], int(changed.size), out_rows,
                        out_srows,
                    )
                    scatter_ms = (time.perf_counter() - s0) * 1e3
                    # seat: the incoming occupants' rows replace them
                    g0 = time.perf_counter()
                    in_rows = jax.device_put(
                        self.registry.gather_client_states(occ_next[changed])
                    )
                    self.client_states = jax.tree_util.tree_map(
                        lambda t, n: t.at[ch].set(n),
                        self.client_states, in_rows,
                    )
                    if has_srows:
                        in_srows = jax.device_put(
                            self.registry.gather_strategy_rows(
                                occ_next[changed]
                            )
                        )
                        self.server_state = self.strategy.scatter_state_rows(
                            self.server_state,
                            jax.tree_util.tree_map(
                                lambda t, n: t.at[ch].set(n),
                                srows_live, in_srows,
                            ),
                        )
                    gather_ms = (time.perf_counter() - g0) * 1e3
                    swap_span.set(scatter_ms=scatter_ms,
                                  gather_ms=gather_ms)
            # restart data for the NEW occupancy on data plan e+1; its
            # val batches/counts also feed this event's eval (the eval
            # runs on the post-swap stack)
            st0 = time.perf_counter()
            staged = self.registry.stage_round(
                occ_next, slots, self._base_entropy, e + 1
            )
            batches_next = jax.device_put(staged["batches"])
            val_batches = jax.device_put(staged["val_batches"])
            val_counts = jnp.asarray(staged["val_counts"])
            wave_counts = jnp.asarray(staged["sample_counts"])
            stage_ms = (time.perf_counter() - st0) * 1e3
            args = [self.server_state, self.client_states,
                    self._async_pending, batches_next,
                    jnp.asarray(plan.arrivals[e - 1]),
                    jnp.asarray(plan.staleness[e - 1]),
                    jnp.asarray(e, jnp.int32), val_batches, val_counts,
                    self._staleness_exponent_input(),
                    None, None,  # no held-out test stacks in cohort mode
                    wave_counts]
            with obs.span("async_event", round=e) as ev_span:
                (self.server_state, self.client_states, self._async_pending,
                 out) = event_jit(*args)
                _, device_wait_s = obs.fence(
                    (out["fit_losses"], out["eval_losses"])
                )
                ev_span.set(device_wait_s=device_wait_s)
            self._count_cohort_roundtrip()
            compiles_after = compile_s_after = None
            if obs.enabled:
                compiles_after = obs.registry.counter(
                    "jax_backend_compiles_total").value
                compile_s_after = obs.registry.counter(
                    "jax_backend_compiles_seconds_total").value
            device_results = {
                "mask": plan.arrivals[e - 1],
                "fit_losses": out["fit_losses"],
                "fit_metrics": out["fit_metrics"],
                "per_client_fit_losses": out["per_client_fit_losses"],
                "eval_losses": out["eval_losses"],
                "eval_metrics": out["eval_metrics"],
            }
            if "telemetry" in out:
                device_results["telemetry"] = out["telemetry"]
            if "quarantine" in out:
                device_results["_quarantine"] = out["quarantine"]
            work = _RoundWork(
                round=e,
                device_results=device_results,
                fit_elapsed_s=time.time() - t0,
                eval_elapsed_s=0.0,
                device_wait_s=device_wait_s,
                compiles_before=compiles_before,
                compile_s_before=compile_s_before,
                compiles_after=compiles_after,
                compile_s_after=compile_s_after,
                async_info=self._async_event_info(plan, e - 1),
                # attribution is by the PRE-swap occupancy: seat s's
                # consumed packet was trained by the occupant seated when
                # s last restarted, who held the seat until this swap
                cohort_meta={"idx": occ_prev},
                cohort_info={
                    "cohort_slots": slots,
                    "cohort_valid": slots,
                    "registry_size": self.registry_size,
                    "registry_dirty_rows": self.registry.dirty_rows,
                    "stage_ms": round(stage_ms, 3),
                    "gather_ms": round(gather_ms, 3),
                    "scatter_ms": round(scatter_ms, 3),
                    "staged_bytes": staged["staged_bytes"],
                    "rounds_per_dispatch": 1,
                    "cohort_draw": "event_plan",
                },
            )
            if consumer is not None:
                consumer.submit_round(
                    e, functools.partial(self._finish_round, work))
                if not self.failure_policy.accept_failures:
                    consumer.flush()
            else:
                self._finish_round(work)
        return occ_next

    def _emit_quarantine_metrics(self, rnd: int, q_np: np.ndarray,
                                 ids: np.ndarray | None = None) -> None:
        """``fl_quarantine_*`` gauges/counters + one ``quarantine`` JSONL
        event from a host copy of the in-graph quarantine mask. Shared by
        the pipelined consumer and the chunked epilogue, so quarantine
        visibility is uniform across execution modes. Transition accounting
        (entered/released) diffs against the previous round's mask.
        ``ids`` (cohort-slot rounds) maps slot positions to registry ids so
        the event names real clients."""
        obs = self.observability
        if not obs.enabled:
            return
        reg = obs.registry
        nz = np.nonzero(np.asarray(q_np) > 0)[0]
        if ids is not None:
            # cohort rounds see only the SAMPLED clients' rows: refresh
            # those ids' standing in the persistent registry-wide view so
            # an unsampled quarantined client doesn't read as "released"
            ids = np.asarray(ids)
            cur = self._cohort_quarantine or set()
            for i in ids:
                cur.discard(int(i))
            cur |= {int(i) for i in ids[nz]}
            self._cohort_quarantine = cur
            active = sorted(cur)
        else:
            active = [int(c) for c in nz]
        prev = self._last_quarantine or []
        entered = sorted(set(active) - set(prev))
        released = sorted(set(prev) - set(active))
        self._last_quarantine = active
        reg.gauge(
            "fl_quarantine_active_clients",
            help="clients currently masked out of aggregation by quarantine",
        ).set(float(len(active)))
        if entered:
            reg.counter(
                "fl_quarantine_entries_total",
                help="clients entering quarantine",
            ).inc(len(entered))
        if released:
            reg.counter(
                "fl_quarantine_releases_total",
                help="clients released from quarantine (probation served)",
            ).inc(len(released))
        if active or entered or released:
            reg.log_event(
                "quarantine", round=rnd, source="strategy",
                active=active, entered=entered, released=released,
            )
        flight = obs.flight_recorder
        if flight is not None:
            # late-attach the round's quarantine evidence to its flight
            # entry (this emitter runs right after _record_round_metrics on
            # both paths); `active` is registry-id-space under cohorts
            flight.attach(
                rnd, quarantine=np.asarray(q_np),
                quarantine_active=list(active),
            )

    def _payload_nbytes(self) -> tuple[int, int]:
        """(broadcast, gather) logical payload bytes per participating client
        — what a wire deployment would serialize each round (the arXiv:
        1610.05492 communication-cost accounting). Computed abstractly via
        ``jax.eval_shape`` (no device work) and cached: payload shapes are
        fixed for the life of the compiled round program."""
        if self._payload_bytes_cache is not None:
            return self._payload_bytes_cache
        tree_bytes = ptu.tree_nbytes
        gp = self._client_part(self.strategy.global_params(self.server_state))
        try:
            payload = jax.eval_shape(
                lambda s: self.strategy.client_payload(s, jnp.zeros((), jnp.int32)),
                self.server_state,
            )
            down_tree = payload.params if hasattr(payload, "params") else payload
        except Exception:  # exotic strategy payloads fall back to the globals
            down_tree = gp
        try:
            up_tree = jax.eval_shape(lambda p: self.exchanger.push(p, p), gp)
        except Exception:
            up_tree = gp
        self._payload_bytes_cache = (tree_bytes(down_tree), tree_bytes(up_tree))
        return self._payload_bytes_cache

    def _compressed_gather_nbytes(self) -> int | None:
        """Estimated compressed client->server wire bytes per participating
        client under the active CompressionConfig — the arithmetic the
        transport codec's compressed frames realize
        (compression.codecs.estimate_wire_nbytes). None without
        compression. Shape-metadata only (eval_shape), cached like
        ``_payload_nbytes``."""
        if not self._compression_active:
            return None
        if self._wire_bytes_cache is not None:
            return self._wire_bytes_cache
        from fl4health_tpu.compression.codecs import estimate_wire_nbytes

        gp = self.strategy.global_params(self.server_state)
        try:
            up_tree = jax.eval_shape(lambda p: self.exchanger.push(p, p), gp)
        except Exception:
            up_tree = gp
        self._wire_bytes_cache = estimate_wire_nbytes(up_tree, self.compression)
        return self._wire_bytes_cache

    # -- fleet ledger (observability/fleet.py) ---------------------------
    def _fleet_absorb_round(
        self, rnd: int, mask, host_fit_losses, telemetry,
        *, registry_ids=None, quarantine_mask=None, failed=(),
        async_info: dict | None = None,
    ) -> "dict | None":
        """Fold one completed round into the fleet ledger. Pure host work
        over arrays this epilogue already materialized (the fused transfer
        / stacked scan outputs) — zero device syncs, so ledger-on runs
        stay bit-identical to ledger-off on every execution mode.

        Called BEFORE the round's state checkpoint is written (both the
        pipelined consumer and the chunked epilogues), so a restored
        ledger is always as-of its frame's round: a resume or supervisor
        rollback replays rounds that absorb exactly once — no
        double-counted participation. Returns the round's fleet facts
        (merged into the round summary), or None when no ledger is armed.
        """
        obs = self.observability
        ledger = obs.fleet_ledger if obs.enabled else None
        if ledger is None:
            return None
        mask_np = np.asarray(mask)
        pos = np.nonzero(mask_np > 0)[0]
        ids_arr = None
        if registry_ids is not None:
            # cohort rounds: slots -> the REGISTRY ids they served
            ids_arr = np.asarray(registry_ids)
            pos = pos[pos < len(ids_arr)]
            part_ids = ids_arr[pos].astype(np.int64)
        else:
            part_ids = pos.astype(np.int64)

        def _sel(row):
            if row is None:
                return None
            arr = np.asarray(row)
            if arr.ndim < 1 or (pos.size and pos.max() >= arr.shape[0]):
                return None
            return arr[pos]

        def _map_ids(idxs):
            if ids_arr is None:
                return [int(c) for c in idxs]
            return [int(ids_arr[int(c)]) for c in idxs
                    if 0 <= int(c) < len(ids_arr)]

        q_in = q_out = None
        if quarantine_mask is not None:
            q = np.asarray(quarantine_mask)
            q_in = _map_ids(np.nonzero(q > 0)[0])
            q_out = _map_ids(np.nonzero(q <= 0)[0])
        fault_ids: list[int] = []
        if self._fault_plan is not None:
            # same seeded host mirror _record_round_metrics logs — a pure
            # recomputation, so absorbing here cannot skew the fault event
            try:
                fault = self._fault_plan.summarize_round(rnd, self.n_clients)
            except Exception:
                fault = None
            if fault:
                fault_ids = _map_ids(sorted(
                    set(fault["dropped"]) | set(fault["corrupted"])
                ))
        down, up = self._payload_nbytes()
        return ledger.absorb_round(
            rnd, part_ids,
            losses=_sel((host_fit_losses or {}).get("backward")),
            update_norms=_sel((telemetry or {}).get("update_norm")),
            nonfinite=_sel((telemetry or {}).get("nonfinite")),
            staleness_pool=(async_info or {}).get("_staleness_values"),
            failed_ids=_map_ids(failed or ()),
            quarantined_ids=q_in,
            unquarantined_ids=q_out,
            fault_ids=fault_ids,
            bytes_down_per_client=down,
            bytes_up_per_client=up,
            registry_size=(self.registry_size if self._cohort_active
                           else self.n_clients),
        )

    def _fleet_snapshot_doc(self) -> "dict | None":
        """The ledger's JSON snapshot for a checkpoint frame's host header
        — None when no ledger is armed, so legacy frames are unchanged."""
        obs = self.observability
        if obs.enabled and obs.fleet_ledger is not None:
            return obs.fleet_ledger.snapshot()
        return None

    def adopt_fleet_snapshot(self, doc: "dict | None") -> None:
        """Checkpoint-resume hook (checkpointing/state.py loaders): adopt
        the frame's fleet-ledger state. A legacy frame (no ``fleet`` key)
        clears the ledger — lifetime history older than the durable record
        is better absent than wrong."""
        ledger = self.observability.fleet_ledger
        if ledger is not None:
            ledger.restore(doc)

    def _record_round_metrics(
        self, rnd: int, rec: RoundRecord, mask, host_fit_losses, failed,
        compiles_before: float, compile_s_before: float, device_wait_s: float,
        *, compiles_after: float | None = None,
        compile_s_after: float | None = None,
        telemetry: dict | None = None,
        async_info: dict | None = None,
        cohort_info: dict | None = None,
        fleet_info: dict | None = None,
        registry_ids: np.ndarray | None = None,
    ) -> dict:
        """Per-round gauges/counters + one JSONL ``round`` event; returns the
        summary dict bridged into every reporter. Runs identically on the
        pipelined path (consumer thread) and the chunked path (post-run
        epilogue), so every per-round gauge is uniform across execution
        modes.

        ``telemetry``: host copy of the round's RoundTelemetry (dict of [C]
        numpy arrays). Scalar summaries merge into the ``round`` event and
        telemetry gauges; the per-client vectors land in one ``telemetry``
        JSONL event.

        ``compiles_after``/``compile_s_after``: counter readings taken by the
        PRODUCER right after the round's dispatches. Under the pipelined loop
        this method runs on the consumer thread while later rounds dispatch;
        reading the live counters here would misattribute their compiles to
        this round, so the producer-captured values win when provided."""
        reg = self.observability.registry
        mask_np = np.asarray(mask)
        participants = int((mask_np > 0).sum())
        down, up = self._payload_nbytes()
        bcast, gather = down * participants, up * participants
        reg.counter("fl_rounds_total", help="completed federated rounds").inc()
        reg.counter(
            "fl_client_failures_total",
            help="clients excluded by the failure policy (non-finite loss)",
        ).inc(len(failed))
        reg.gauge(
            "fl_participating_clients",
            help="clients sampled into the current round",
        ).set(participants)
        row = np.asarray(host_fit_losses.get("backward", np.zeros_like(mask_np)))
        sel = row[(mask_np > 0) & np.isfinite(row)]
        loss_std = float(sel.std()) if sel.size else 0.0
        loss_spread = float(sel.max() - sel.min()) if sel.size else 0.0
        reg.gauge(
            "fl_fit_loss_std",
            help="dispersion of participating clients' training loss",
        ).set(loss_std)
        reg.gauge(
            "fl_fit_loss_spread",
            help="straggler proxy: max-min participating client training loss",
        ).set(loss_spread)
        reg.counter(
            "fl_broadcast_bytes_total",
            help="logical server->client payload bytes (what a wire "
                 "deployment would serialize per round)",
        ).inc(bcast)
        reg.counter(
            "fl_gather_bytes_total",
            help="logical client->server payload bytes",
        ).inc(gather)
        gather_wire = None
        wire_per_client = self._compressed_gather_nbytes()
        if wire_per_client is not None:
            # compressed exchange active: fl_wire_* distinguishes the
            # logical payload from what the compressed frames would ship —
            # the SAME accounting helper the transport codec bumps for
            # real frames, under direction="gather"
            from fl4health_tpu.transport.codec import account_wire

            gather_wire = wire_per_client * participants
            account_wire(gather, gather_wire, "gather")
        if compiles_after is None:
            compiles_after = reg.counter("jax_backend_compiles_total").value
        if compile_s_after is None:
            compile_s_after = reg.counter(
                "jax_backend_compiles_seconds_total").value
        summary = {
            "round": rnd,
            "execution_mode": self._active_execution_mode,
            "compiles": compiles_after - compiles_before,
            "compile_s": compile_s_after - compile_s_before,
            "device_wait_s": device_wait_s,
            "fit_s": rec.fit_elapsed_s,
            "eval_s": rec.eval_elapsed_s,
            "host_s": max(
                0.0, rec.fit_elapsed_s + rec.eval_elapsed_s - device_wait_s
            ),
            "broadcast_bytes": bcast,
            "gather_bytes": gather,
            "participants": participants,
            "failures": len(failed),
            "fit_loss_std": loss_std,
            "fit_loss_spread": loss_spread,
        }
        if gather_wire is not None:
            summary["gather_bytes_wire"] = gather_wire
            summary["wire_compression_ratio"] = (
                gather / gather_wire if gather_wire > 0 else None
            )
        if async_info is not None:
            # buffered-async attribution (absent on sync logs, so legacy
            # perf_report tables stay byte-stable): buffer occupancy,
            # per-update staleness and the virtual arrival-driven cadence
            # of this event — the "round cadence set by arrival rate"
            # numbers the async mode exists for
            stal_values = async_info.pop("_staleness_values", [])
            summary.update(async_info)
            reg.gauge(
                "fl_async_buffer_occupancy",
                help="updates consumed by the current buffer-fill event",
            ).set(float(async_info.get("async_buffer", 0)))
            reg.gauge(
                "fl_async_round_cadence_vs",
                help="virtual seconds between consecutive aggregation "
                     "events (arrival-driven round cadence)",
            ).set(float(async_info.get("async_cadence_vs", 0.0)))
            hist = reg.histogram(
                "fl_async_staleness",
                help="staleness (server versions) of consumed updates",
                buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
            )
            for s in stal_values:
                hist.observe(float(s))
        if cohort_info is not None:
            # cohort-slot attribution (absent on dense logs, so legacy
            # perf_report tables stay byte-stable): slot occupancy, the
            # registry's size/dirty-row facts, and the staging/gather/
            # scatter walls the O(K) claim is judged by
            summary.update(cohort_info)
            reg.gauge(
                "fl_registry_clients",
                help="clients in the host-resident cohort registry",
            ).set(float(cohort_info["registry_size"]))
            reg.gauge(
                "fl_registry_dirty_rows",
                help="registry clients with materialized (participated) "
                     "state rows — registry host memory is O(this), not "
                     "O(registry)",
            ).set(float(cohort_info["registry_dirty_rows"]))
            reg.gauge(
                "fl_registry_cohort_valid",
                help="real (non-padded) slots in the current round's "
                     "sampled cohort",
            ).set(float(cohort_info["cohort_valid"]))
            reg.counter(
                "fl_registry_staged_bytes_total",
                help="host bytes staged into slot tensors per round "
                     "(train + val batches)",
            ).inc(int(cohort_info["staged_bytes"]))
        if fleet_info is not None:
            # fleet-ledger attribution (absent with the ledger off, so
            # legacy perf_report tables stay byte-stable): new-client
            # count, participation skew and the lifetime straggler tail
            summary.update({k: v for k, v in fleet_info.items()
                            if v is not None})
            ledger = self.observability.fleet_ledger
            reg.gauge(
                "fl_fleet_clients_seen",
                help="clients with a fleet-ledger lifetime record (ledger "
                     "host memory is O(this), not O(registry))",
            ).set(float(len(ledger)))
            reg.counter(
                "fl_fleet_new_clients_total",
                help="first-ever participations absorbed by the fleet "
                     "ledger",
            ).inc(int(fleet_info.get("participants_new") or 0))
            if fleet_info.get("participation_gini") is not None:
                reg.gauge(
                    "fl_fleet_participation_gini",
                    help="participation skew over seen clients (0 = even, "
                         "->1 = a few clients do everything)",
                ).set(float(fleet_info["participation_gini"]))
            if fleet_info.get("straggler_p99") is not None:
                reg.gauge(
                    "fl_fleet_straggler_p99",
                    help="p99 of the lifetime participation-gap "
                         "distribution, in rounds (sketched)",
                ).set(float(fleet_info["straggler_p99"]))
            reg.gauge(
                "fl_fleet_ledger_bytes",
                help="approximate host bytes held by the fleet ledger + "
                     "its sketches (registry-size-invariant)",
            ).set(float(ledger.nbytes()))
        if self._precision_active:
            # precision attribution (absent on f32 logs, so legacy
            # perf_report tables stay byte-stable): the dtype that produced
            # this round's device time — and thus its MFU/tflops numbers
            summary["compute_dtype"] = self.precision.compute_dtype_name
            if self._precision_scaling:
                summary["loss_scale_mode"] = (
                    self.precision.resolved_loss_scale
                )
        if telemetry is not None:
            t_summary = telem.summarize_host(telemetry, mask_np)
            summary.update(t_summary)
            reg.gauge(
                "fl_fit_grad_norm_max",
                help="max per-client gradient norm this round "
                     "(post transform_gradients)",
            ).set(t_summary["grad_norm_max"])
            reg.gauge(
                "fl_fit_update_norm_min",
                help="min participating client update norm (dead-client "
                     "proxy)",
            ).set(t_summary["update_norm_min"])
            reg.gauge(
                "fl_fit_divergence_max",
                help="max client weight divergence from the aggregated "
                     "global",
            ).set(t_summary["divergence_max"])
            reg.gauge(
                "fl_dp_clip_fraction",
                help="mean fraction of examples clipped by the DP path "
                     "(NaN without DP)",
            ).set(t_summary["clip_fraction"])
            reg.gauge(
                "fl_nonfinite_values",
                help="non-finite entries across participating clients' "
                     "params/losses this round",
            ).set(t_summary["nonfinite"])
            reg.log_event(
                "telemetry", round=rnd,
                **{k: np.asarray(v, np.float64).tolist()
                   for k, v in telemetry.items()},
            )
        # MEASURED throughput denominator: the fenced device-execution time
        # when observability fenced this round (it excludes XLA compiles by
        # construction), else the round wall minus its compile delta — a
        # compile-inflated wall would understate MFU ~100x on exactly the
        # big-compile configs this number exists for (round 1, and every
        # amortized chunked round).
        wall = rec.fit_elapsed_s + rec.eval_elapsed_s
        exec_s = (device_wait_s if device_wait_s > 0
                  else wall - summary["compile_s"])
        n_mesh = self._program_builder.n_devices
        if self._program_builder.mesh is not None:
            # mesh-run extras (absent on single-chip logs, so legacy
            # perf_report tables stay byte-stable): devices/axis facts plus
            # the per-chip local-step throughput over device-execution time
            summary["mesh_devices"] = n_mesh
            summary["mesh_client_axis"] = self._program_builder.client_axis_size
            if self._steps_per_client_cache is None:
                if self._cohort_active:
                    # slot rounds: every valid slot runs the registry-wide
                    # step budget (padding steps are masked no-ops but a
                    # finer per-cohort count would vary per round)
                    self._steps_per_client_cache = np.full(
                        (self.n_clients,), float(self.registry.train_steps)
                    )
                else:
                    self._steps_per_client_cache = np.asarray(
                        self._round_plan(1)[2]
                    ).sum(axis=1)
            steps = float(
                (self._steps_per_client_cache * (mask_np > 0)).sum()
            )
            if steps > 0 and exec_s > 0:
                summary["steps_per_s_per_chip"] = steps / exec_s / n_mesh
                reg.gauge(
                    "fl_round_steps_per_s_per_chip",
                    help="participating clients' local steps per second "
                         "per mesh device (device-execution time)",
                ).set(summary["steps_per_s_per_chip"])
        if self._round_program_flops and exec_s > 0:
            # build-time cost_analysis FLOPs over device-execution time —
            # hardware-grounded, unlike bench.py's old analytic formula.
            # mfu_pct only where the chip's peak is known (device_specs);
            # never a made-up percentage. On a mesh the denominator is the
            # whole mesh's wall, so MFU/tflops divide down to PER-CHIP —
            # the honest utilization of each device, comparable across
            # mesh sizes.
            achieved = self._round_program_flops / exec_s
            summary["program_flops_round"] = self._round_program_flops
            summary["program_exec_s"] = exec_s
            summary["tflops_measured"] = achieved / 1e12
            reg.gauge(
                "fl_round_tflops_measured",
                help="measured TFLOP/s this round (cost-model FLOPs / "
                     "device-execution time, whole mesh)",
            ).set(achieved / 1e12)
            if self._program_builder.mesh is not None:
                summary["tflops_per_chip"] = achieved / n_mesh / 1e12
                reg.gauge(
                    "fl_round_tflops_per_chip",
                    help="measured TFLOP/s per mesh device this round",
                ).set(summary["tflops_per_chip"])
            mfu = device_specs.mfu_pct(achieved / n_mesh, self._device_kind)
            if mfu is not None:
                summary["mfu_pct"] = mfu
                reg.gauge(
                    "fl_round_mfu_pct",
                    help="measured model FLOPs utilization vs the chip's "
                         "bf16 peak (per chip on a mesh)",
                ).set(mfu)
        fault = None
        if self._fault_plan is not None:
            # host mirror of the round's seeded in-graph fault draws — the
            # log reports exactly what the compiled program injected
            try:
                fault = self._fault_plan.summarize_round(rnd, self.n_clients)
            except Exception:
                logging.getLogger(__name__).warning(
                    "fault-plan summary failed for round %d", rnd,
                    exc_info=True,
                )
                fault = None
            if fault:
                reg.counter(
                    "fl_resilience_faults_injected_total",
                    help="client faults injected by the active FaultPlan "
                         "(dropouts + corruptions)",
                ).inc(len(fault["dropped"]) + len(fault["corrupted"]))
                reg.log_event("fault", **fault)
                summary["faults_injected"] = (
                    len(fault["dropped"]) + len(fault["corrupted"])
                )
        reg.log_event("round", **summary)
        flight = self.observability.flight_recorder
        if flight is not None:
            # flight-recorder feed: every array here is host data this
            # epilogue already materialized (the fused transfer / stacked
            # scan outputs) — recording adds zero device syncs, and the
            # ring stays O(window x cohort slots) by construction
            flight.record_round(
                rnd, summary,
                fit_loss=rec.fit_losses.get("backward"),
                eval_loss=rec.eval_losses.get("checkpoint"),
                mask=mask_np,
                telemetry=telemetry,
                registry_ids=registry_ids,
                fault=fault or None,
            )
            reg.counter(
                "fl_flightrec_rounds_total",
                help="rounds captured into the flight-recorder ring",
            ).inc()
            reg.gauge(
                "fl_flightrec_ring_bytes",
                help="host bytes of the flight-recorder ring's array "
                     "payload (bounded: O(window x cohort slots))",
            ).set(float(flight.nbytes()))
            reg.gauge(
                "fl_flightrec_window",
                help="flight-recorder ring capacity in rounds",
            ).set(float(flight.window))
        self.observability.tracer.counter(
            "fl_round_time_s", fit=rec.fit_elapsed_s, eval=rec.eval_elapsed_s
        )
        # operations plane (armed via Observability(slo=/admin_token=)):
        # fold this summary into the serving-KPI time-series and evaluate
        # the SLO policy — same host floats as above, zero extra syncs; a
        # shared no-op when unarmed
        self.observability.observe_round_kpis(
            rnd, summary,
            fit_loss=rec.fit_losses.get("backward"),
            eval_loss=rec.eval_losses.get("checkpoint"),
        )
        return summary

    @property
    def global_params(self):
        self._ensure_shared()
        return self.strategy.global_params(self.server_state)

    def _ensure_shared(self) -> None:
        """Give the shared leaves their initial values if nothing has been
        installed over them yet (they are abstract until first needed)."""
        if self._per_client is None:
            return
        from fl4health_tpu.strategies.shared_base import materialized

        if not materialized(self.server_state):
            self.server_state = self.server_state.replace(
                shared=self._make_shared()
            )

    def _report_parameter_split(self) -> None:
        """The predicate's counts, once at build: gauges for a scraped
        metrics page and one ``parameter_split`` event."""
        per_client, shared = self.strategy.split(
            self.strategy.global_params(self.server_state)
        )
        down, up = self._payload_nbytes()
        gauges = (
            ("client_param_bytes", ptu.tree_nbytes(per_client),
             "bytes of the leaves each client holds (trains, exchanges)"),
            ("shared_param_bytes", ptu.tree_nbytes(shared),
             "bytes of the leaves that exist once, outside every client's "
             "copy"),
            ("exchanged_bytes_per_round", (down + up) * self.n_clients,
             "payload bytes down and up, all clients, one round"),
        )
        model = self.logic.model
        if model.build_gauges is not None:
            batch_shape = (self.batch_size,
                           *self.datasets[0].x_train.shape[1:])
            gauges += tuple(
                (name, value, "static fact of the model's layers, from its "
                 "build_gauges")
                for name, value in model.build_gauges(
                    batch_shape, self.n_clients).items())
        obs = self.observability
        for name, value, text in gauges:
            obs.gauge(name, help=text).set(float(value))
        obs.log_event(
            "parameter_split",
            per_client_leaves=len(jax.tree_util.tree_leaves(per_client)),
            shared_leaves=len(jax.tree_util.tree_leaves(shared)),
            clients=self.n_clients,
            **{name: value for name, value, _ in gauges},
        )

    def _client_part(self, params):
        """The per-client leaves of a whole-model tree (the tree itself
        when every leaf is per client)."""
        if self._per_client is None:
            return params
        return ptu.split_by_path(params, self._per_client)[0]

    def set_global_params(self, params, broadcast_to_clients: bool = True) -> None:
        """Install externally-produced weights (warm-up injection, pretrained
        checkpoint import — preprocessing/checkpoint_io.py) as the global
        model. With ``broadcast_to_clients`` every client's full local tree
        resets to the same weights, the reference's round-1
        initialize_all_model_weights broadcast (basic_client.py:205) — the
        only path by which never-exchanged subtrees (personal layers, frozen
        LoRA base kernels under a lora_exchanger) can receive pretrained
        values."""
        params = jax.tree_util.tree_map(jnp.asarray, params)
        ref = self.strategy.global_params(self.server_state)
        if (jax.tree_util.tree_structure(params)
                != jax.tree_util.tree_structure(ref)):
            raise ValueError(
                "set_global_params: pytree structure does not match the "
                "model's params (run the checkpoint through WarmedUpModule/"
                "warm_up_from_file against this model's init first)"
            )
        any_dtype_mismatch = False
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(ref)[0],
        ):
            if a.shape != b.shape:
                raise ValueError(
                    f"set_global_params: leaf {pa} has shape {a.shape}, "
                    f"model expects {b.shape}"
                )
            any_dtype_mismatch |= a.dtype != b.dtype
        if any_dtype_mismatch:
            # a float64/float16 checkpoint leaf would silently change the
            # compiled program's input signature (recompile) or its
            # precision; cast to the model's dtype instead (AFTER the full
            # shape loop — a later bad-shape leaf must still raise above)
            params = jax.tree_util.tree_map(
                lambda x, y: x.astype(y.dtype), params, ref
            )
        # nesting-safe: a wrapper strategy's state (compression/quarantine)
        # carries the params inside its .inner chain, not at top level
        from fl4health_tpu.strategies.base import replace_global_params

        self.server_state = replace_global_params(
            self.strategy, self.server_state, params
        )
        if broadcast_to_clients:
            n = self.n_clients
            self.client_states = self.client_states.replace(
                params=jax.tree_util.tree_map(
                    lambda x: jnp.stack([x] * n), self._client_part(params)
                )
            )
