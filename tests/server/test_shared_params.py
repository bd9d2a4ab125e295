"""Parameters that exist once (``ModelDef.per_client``): C clients' adapters
over ONE base train exactly what C whole-model clients train under
``masked_optimizer`` + ``lora_exchanger``, on both drivers; the shared leaves
come back bit for bit; the gauges count adapters only; and a model without
the predicate builds the program it always built."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fl4health_tpu.clients import engine
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.transformer import TransformerClassifier
from fl4health_tpu.observability import Observability
from fl4health_tpu.observability.registry import MetricsRegistry
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.strategies.fedopt import FedOpt
from fl4health_tpu.strategies.shared_base import (SharedBaseStrategy,
                                                  materialized)
from fl4health_tpu.utils import peft

PREDICATE = peft.per_client_predicate()
SIZES = dict(vocab_size=50, n_classes=3, d_model=16, n_heads=2, n_layers=2,
             d_ff=32, max_len=8, lora_rank=2)
MODULE = TransformerClassifier(**SIZES)


class OverOneBase(TransformerClassifier):
    """The same module, saying which of its leaves are per client: the one
    way a model declares the split (``engine.from_flax`` reads it)."""

    def per_client_param(self, path):
        return PREDICATE(path)


class EveryLeafPerClient(TransformerClassifier):
    def per_client_param(self, path):
        return True


SHARED = OverOneBase(**SIZES)
MODES = {"pipelined": "pipelined_per_round", "chunked": "chunked_scan"}


def _datasets():
    rng = np.random.default_rng(0)

    def one(n):
        x = rng.integers(1, 50, (n, 8)).astype(np.int32)
        y = (x[:, 0] % 3).astype(np.int32)
        return ClientDataset(x[:n - 4], y[:n - 4], x[n - 4:], y[n - 4:])

    return [one(12), one(20), one(16)]


@pytest.fixture(scope="module")
def w0():
    params = MODULE.init(jax.random.PRNGKey(5), _datasets()[0].x_train[:1],
                         train=False)["params"]
    # lora_b off zero, so that lora_a has a gradient from the first step
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a + 0.02 if "lora_b" in jax.tree_util.keystr(p) else a,
        params)


def _sim(w0, form, mode, strategy=None, observability=None):
    """form: 'shared' (the predicate through the engine), 'whole' (every
    client a frozen copy of the base), 'plain' (no predicate, no mask)."""
    tx, exchanger = optax.sgd(0.005), None
    if form == "whole":
        tx = peft.masked_optimizer(tx, peft.lora_trainable_mask(w0))
        exchanger = peft.lora_exchanger()
    model = engine.from_flax(SHARED if form == "shared" else MODULE)
    sim = FederatedSimulation(
        logic=engine.ClientLogic(model, engine.masked_cross_entropy), tx=tx,
        strategy=strategy or FedAvg(), datasets=_datasets(), batch_size=4,
        metrics=MetricManager((efficient.accuracy(),)), local_steps=2, seed=3,
        execution_mode=mode, exchanger=exchanger, observability=observability)
    sim.set_global_params(w0)
    return sim


def _losses(sim):
    return ([float(r.fit_losses["backward"]) for r in sim.history],
            [float(r.eval_losses["checkpoint"]) for r in sim.history])


@pytest.mark.parametrize("mode", sorted(MODES))
def test_one_base_trains_what_whole_model_clients_train(w0, mode):
    shared, whole = _sim(w0, "shared", mode), _sim(w0, "whole", mode)
    shared.fit(3)
    whole.fit(3)
    assert shared._active_execution_mode == MODES[mode]
    for a, b in zip(_losses(shared), _losses(whole)):
        np.testing.assert_allclose(a, b, rtol=5e-5)
    adapters = ptu.split_by_path(shared.global_params, PREDICATE)[0]
    theirs = ptu.split_by_path(whole.global_params, PREDICATE)[0]
    for a, b in zip(jax.tree_util.tree_leaves(adapters),
                    jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    # what each holds: the adapters alone, against the whole model per client
    assert (len(jax.tree_util.tree_leaves(shared.client_states.params))
            == len(jax.tree_util.tree_leaves(adapters)) == 26)
    assert len(jax.tree_util.tree_leaves(whole.client_states.params)) == 62
    assert (jax.tree_util.tree_structure(shared.client_states.opt_state)
            == jax.tree_util.tree_structure(
                optax.sgd(0.005).init(adapters)))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_shared_leaves_come_back_bit_for_bit(w0, mode):
    sim = _sim(w0, "shared", mode)
    sim.fit(2)
    got = ptu.split_by_path(sim.global_params, PREDICATE)[1]
    want = ptu.split_by_path(w0, PREDICATE)[1]
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))
    # and the whole tree has every leaf of the model
    assert (jax.tree_util.tree_structure(sim.global_params)
            == jax.tree_util.tree_structure(w0))


def test_gauges_and_event_count_the_split(w0):
    obs = Observability(registry=MetricsRegistry())
    sim = _sim(w0, "shared", "pipelined", observability=obs)
    per_client, shared = ptu.split_by_path(w0, PREDICATE)
    gauge = obs.registry.snapshot().__getitem__
    assert gauge("client_param_bytes") == ptu.tree_nbytes(per_client)
    assert gauge("shared_param_bytes") == ptu.tree_nbytes(shared)
    # down and up, three clients, adapters only
    assert gauge("exchanged_bytes_per_round") == 2 * 3 * ptu.tree_nbytes(
        per_client)
    event = [e for e in obs.registry.events
             if e["event"] == "parameter_split"]
    assert len(event) == 1 and event[0]["per_client_leaves"] == 26
    assert event[0]["shared_leaves"] == 36 and event[0]["clients"] == 3
    assert isinstance(sim.strategy, SharedBaseStrategy)


def test_shared_leaves_are_abstract_until_first_needed():
    model = engine.from_flax(SHARED)
    sim = FederatedSimulation(
        logic=engine.ClientLogic(model, engine.masked_cross_entropy),
        tx=optax.sgd(0.005), strategy=FedAvg(), datasets=_datasets(),
        batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, seed=3, execution_mode="pipelined")
    assert not materialized(sim.server_state)
    sim.fit(1)  # no weights installed: the model's own init, made on demand
    assert materialized(sim.server_state)
    plain = FederatedSimulation(
        logic=engine.ClientLogic(engine.from_flax(MODULE),
                                 engine.masked_cross_entropy),
        tx=optax.sgd(0.005), strategy=FedAvg(), datasets=_datasets(),
        batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, seed=3, execution_mode="pipelined")
    # the split init gives the whole init's values (it runs under jit, the
    # whole one eagerly: an ulp apart in the normal sampler)
    for a, b in zip(jax.tree_util.tree_leaves(ptu.split_by_path(
            sim.global_params, PREDICATE)[1]), jax.tree_util.tree_leaves(
            ptu.split_by_path(plain.global_params, PREDICATE)[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-8)


def test_a_server_optimizer_sees_adapters_only(w0):
    sim = _sim(w0, "shared", "pipelined",
               strategy=FedOpt(optax.sgd(1.0, momentum=0.5)))
    sim.fit(2)
    inner = sim.server_state.inner
    n_adapters = len(jax.tree_util.tree_leaves(
        ptu.split_by_path(w0, PREDICATE)[0]))
    assert len(jax.tree_util.tree_leaves(inner.params)) == n_adapters
    moments = [leaf for leaf in jax.tree_util.tree_leaves(inner)
               if hasattr(leaf, "shape") and leaf.ndim > 0]
    assert len(moments) == 2 * n_adapters  # params and one momentum each


@pytest.mark.parametrize("kwargs", [
    {"mesh": "mesh"}, {"cohort": "cohort"}, {"async_config": "async"},
    {"compression": "compression"}])
def test_unsupported_compositions_say_so(w0, kwargs):
    from fl4health_tpu.compression.config import CompressionConfig
    from fl4health_tpu.parallel.program import MeshConfig
    from fl4health_tpu.server.async_schedule import AsyncConfig
    from fl4health_tpu.server.registry import CohortConfig

    real = {"mesh": MeshConfig(), "cohort": CohortConfig(slots=2),
            "async": AsyncConfig(buffer_size=2),
            "compression": CompressionConfig(topk_fraction=0.5)}
    kwargs = {k: real[v] for k, v in kwargs.items()}
    model = engine.from_flax(SHARED)
    with pytest.raises(NotImplementedError, match="shared parameters"):
        FederatedSimulation(
            logic=engine.ClientLogic(model, engine.masked_cross_entropy),
            tx=optax.sgd(0.005), strategy=FedAvg(), datasets=_datasets(),
            batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
            local_steps=2, **kwargs)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_model_without_the_predicate_builds_the_program_it_always_built(
        w0, mode):
    """No wrapper, no cast scope, no event; and a predicate that is true of
    every leaf (an empty shared half) trains bit for bit what no predicate
    trains."""
    obs = Observability(registry=MetricsRegistry())
    plain = _sim(w0, "plain", mode, observability=obs)
    assert type(plain.strategy) is FedAvg and plain._per_client is None
    assert not [e for e in obs.registry.events
                if e["event"] == "parameter_split"]
    args = (plain.server_state, plain.client_states, plain._round_batches(1),
            jnp.ones((3,), jnp.float32), jnp.asarray(1, jnp.int32),
            plain._val_batches()[0])
    text = jax.jit(plain._fit_round_fn).lower(*args).as_text(debug_info=True)
    assert "fl_layer::shared_cast" not in text
    everything = FederatedSimulation(
        logic=engine.ClientLogic(
            engine.from_flax(EveryLeafPerClient(**SIZES)),
            engine.masked_cross_entropy),
        tx=optax.sgd(0.005), strategy=FedAvg(), datasets=_datasets(),
        batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, seed=3, execution_mode=mode)
    everything.set_global_params(w0)
    plain.fit(2)
    everything.fit(2)
    assert _losses(plain) == _losses(everything)
    for a, b in zip(jax.tree_util.tree_leaves(plain.global_params),
                    jax.tree_util.tree_leaves(everything.global_params)):
        assert bool(jnp.array_equal(a, b))
