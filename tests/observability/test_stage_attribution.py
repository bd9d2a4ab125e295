"""Stage scopes (observability/stages.py).

The pinned contracts:

- the ``fl_stage::`` named-scope markers are METADATA-ONLY — training is
  bit-identical with the scopes on vs off (params AND trajectories) on
  every execution mode, including a cohort-slot run;
- every spine stage's scope survives into the COMPILED program that runs
  its seam (``compiled.as_text()`` holds each op's ``op_name`` name stack):
  that text is where a profiler trace's op metadata comes from, so a scope
  missing here reads ``null`` in ``local_train_ms_per_round`` /
  ``server_update_ms_per_round`` and ``tools/roofline_report.py``;
- both benchmark model families keep ``local_train`` and ``server_update``
  in their per-round fit program.
"""

import contextlib
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from fl4health_tpu.clients import engine
from fl4health_tpu.compression import CompressionConfig
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.models.deepseek import DeepseekV2Classifier
from fl4health_tpu.models.jamba import JambaClassifier
from fl4health_tpu.models.transformer import TransformerClassifier
from fl4health_tpu.observability import (
    MetricsRegistry,
    Observability,
    Tracer,
)
from fl4health_tpu.observability import stages as stage_attr
from fl4health_tpu.observability.introspect import abstractify
from fl4health_tpu.privacy import dpsgd
from fl4health_tpu.resilience import RobustFedAvg
from fl4health_tpu.server.registry import CohortConfig
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg

pytestmark = pytest.mark.roofline

N_CLASSES = 3


def _obs():
    return Observability(enabled=True, tracer=Tracer(),
                         registry=MetricsRegistry())


def _mlp_sim(n=3, mode="auto", **kwargs):
    datasets = []
    for i in range(n):
        x, y = synthetic_classification(
            jax.random.PRNGKey(i), 40, (6,), N_CLASSES
        )
        datasets.append(ClientDataset(x[:32], y[:32], x[32:], y[32:]))
    args = dict(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(12,), n_outputs=N_CLASSES)),
            engine.masked_cross_entropy,
        ),
        tx=optax.sgd(0.05),
        strategy=FedAvg(),
        datasets=datasets,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_epochs=1,
        seed=5,
        observability=_obs(),
        execution_mode=mode,
    )
    args.update(kwargs)
    return FederatedSimulation(**args)


def _token_sim(module):
    """Three clients of a toy token classifier on the per-round driver: the
    benchmark cells' job at toy size."""
    rng = np.random.default_rng(0)
    datasets = []
    for n in (12, 20, 16):
        x = rng.integers(1, 50, (n, 8)).astype(np.int32)
        y = (x[:, 0] % N_CLASSES).astype(np.int32)
        datasets.append(ClientDataset(x[:n - 4], y[:n - 4],
                                      x[n - 4:], y[n - 4:]))
    return FederatedSimulation(
        logic=engine.ClientLogic(engine.from_flax(module),
                                 engine.masked_cross_entropy),
        tx=optax.sgd(0.005), strategy=FedAvg(), datasets=datasets,
        batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, seed=3, execution_mode="pipelined",
        observability=_obs())


def _compiled_texts(sim):
    """name -> optimised-HLO text of every round program ``fit()`` builds:
    what the introspector is asked about at build time, compiled here in
    its place."""
    texts = {}

    def compile_instead(name, jitted, args, **_):
        texts[name] = jitted.lower(*abstractify(args)).compile().as_text()

    sim.observability.introspector.introspect_jit = compile_instead
    sim.fit(1)
    return texts


def _scopes(text):
    return set(re.findall(re.escape(stage_attr.STAGE_PREFIX) + r"(\w+)", text))


def _flat(tree):
    return np.asarray(jax.flatten_util.ravel_pytree(jax.device_get(tree))[0])


def _run(scopes_on, rounds=3, **kwargs):
    ctx = (contextlib.nullcontext() if scopes_on
           else stage_attr.disabled())
    with ctx:
        sim = _mlp_sim(**kwargs)
        history = sim.fit(rounds)
    params = _flat(sim.strategy.global_params(sim.server_state))
    losses = np.asarray(
        [h.eval_losses["checkpoint"] for h in history], dtype=np.float64
    )
    return params, losses


class TestStageOf:
    def test_basic(self):
        assert stage_attr.stage_of("jit(f)/fl_stage::dp_clip/add") == "dp_clip"

    def test_innermost_wins(self):
        path = "jit(f)/fl_stage::server_update/fl_stage::robust_aggregate/x"
        assert stage_attr.stage_of(path) == "robust_aggregate"

    def test_none_without_marker(self):
        assert stage_attr.stage_of("jit(f)/transpose/add") is None
        assert stage_attr.stage_of(None) is None
        assert stage_attr.stage_of("") is None


class TestBitIdentity:
    """Scopes on vs off: params AND trajectories bitwise equal — named
    scopes must never change what XLA computes."""

    @pytest.mark.parametrize("kwargs", [
        dict(mode="pipelined"),
        dict(mode="chunked"),
        dict(cohort=CohortConfig(slots=3), mode="chunked"),
    ], ids=["pipelined", "chunked", "cohort_chunked"])
    def test_scopes_change_nothing(self, kwargs):
        pa, la = _run(True, **kwargs)
        pb, lb = _run(False, **kwargs)
        np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(la, lb)


class TestScopesInCompiledPrograms:
    def test_client_step_and_server_update_on_both_drivers(self):
        """The per-round fit program and the chunked scan both carry the two
        scopes every cell's stage metrics read, and the backward pass of
        the client step stays under ``local_train`` (autodiff keeps the
        name stack)."""
        per_round = _compiled_texts(_mlp_sim(mode="pipelined"))
        chunk = _compiled_texts(_mlp_sim(mode="chunked"))
        assert set(chunk) == {"fit_chunk_eval"}
        for text in (per_round["fit_round_t"], chunk["fit_chunk_eval"]):
            assert _scopes(text) == {"local_train", "server_update"}
            assert any("fl_stage::local_train" in line and "transpose(" in line
                       for line in text.splitlines())

    def test_cohort_exchange_is_the_cohort_chunks_own(self):
        """The in-graph gather/scatter of the registry window exists in the
        chunk scan only: the slot programs beside it draw on the host."""
        texts = _compiled_texts(
            _mlp_sim(cohort=CohortConfig(slots=3), mode="chunked"))
        assert _scopes(texts["fit_cohort_chunk"]) == {
            "cohort_exchange", "local_train", "server_update"}
        assert _scopes(texts["fit_round_t"]) == {"local_train",
                                                 "server_update"}


@functools.cache
def _seam_text(seam):
    """The compiled text of the smallest program that runs ``seam``."""
    if seam == "fused_dp":
        # the fused clip kernel is opt-in at its one call site and no client
        # logic passes the flag (ROADMAP S8): the seam's smallest program is
        # that call itself, under the clients' vmap the engine would put it in
        grads = {"w": jnp.ones((2, 6, 4, 3)), "b": jnp.ones((2, 6, 3))}

        def clipped(g, rng):
            return dpsgd.noisy_clipped_mean_grads(
                g, jnp.ones((6,)), rng, 0.5, 1.0, use_fused_kernel=True)

        return jax.jit(jax.vmap(clipped)).lower(
            grads, jax.random.split(jax.random.PRNGKey(0), 2)
        ).compile().as_text()
    program, kwargs = {
        "plain": ("fit_round_t", dict(mode="pipelined")),
        "compressed": ("fit_round_t", dict(
            mode="pipelined",
            compression=CompressionConfig(topk_fraction=0.25, quant_bits=8,
                                          rotation=True))),
        "robust": ("fit_round_t", dict(mode="pipelined",
                                       strategy=RobustFedAvg("median"))),
        "cohort_chunk": ("fit_cohort_chunk", dict(
            cohort=CohortConfig(slots=3), mode="chunked")),
    }[seam]
    return _compiled_texts(_mlp_sim(**kwargs))[program]


SEAM_OF = {
    "local_train": "plain",
    "dp_clip": "fused_dp",
    "rotation": "compressed",
    "topk": "compressed",
    "quantize": "compressed",
    "robust_aggregate": "robust",
    "server_update": "plain",
    "cohort_exchange": "cohort_chunk",
}


@pytest.mark.parametrize("stage", stage_attr.SPINE_STAGES)
def test_every_spine_stage_names_its_seam_in_the_compiled_program(stage):
    assert stage in _scopes(_seam_text(SEAM_OF[stage]))


@functools.cache
def _model_fit_text(family):
    if family == "transformer":
        module = TransformerClassifier(
            vocab_size=50, n_classes=N_CLASSES, d_model=16, n_heads=2,
            n_layers=2, d_ff=32, max_len=8)
    elif family == "deepseek":
        # one dense and one expert layer over a shared base, 4 of 8 experts
        # held, through the flash calls at two head widths
        module = DeepseekV2Classifier(
            vocab_size=50, n_classes=N_CLASSES, d_model=16, n_layers=2,
            d_ff=32, n_heads=2, q_lora_rank=8, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, d_expert=8,
            n_routed_experts=8, experts_held=4, first_expert_held=2,
            n_group=4, topk_group=2, top_k=3, lora_rank=2, remat=True,
            dtype=jnp.bfloat16,
            attention_fn=functools.partial(flash_attention, causal=True,
                                           block_q=8, block_k=8))
    else:
        # one Mamba layer and one attention layer over a shared base
        module = JambaClassifier(
            vocab_size=50, n_classes=N_CLASSES, d_model=16, n_layers=2,
            d_ff=32, n_heads=2, n_kv_heads=1, d_state=4, dt_rank=4,
            attn_layer_period=2, attn_layer_offset=1, lora_rank=2)
    return _compiled_texts(_token_sim(module))["fit_round_t"]


@pytest.mark.parametrize("stage", ["local_train", "server_update"])
@pytest.mark.parametrize("family", ["transformer", "jamba", "deepseek"])
def test_the_cells_model_families_keep_both_stage_scopes(family, stage):
    """``local_train_ms_per_round`` and ``server_update_ms_per_round`` read
    exactly these two strings out of a trace of ``fit_round_t``."""
    assert f"fl_stage::{stage}" in _model_fit_text(family)


@pytest.mark.parametrize("scope,op", [
    ("mla_attention", "dot"), ("mla_flash", "dynamic_slice"),
    ("moe", "dot_general"), ("moe_router", "reduce_max"),
    ("moe_experts", "dot_general"), ("shared_experts", "dot"),
    ("shared_cast", "convert")])
def test_the_expert_familys_layer_scopes_reach_the_compiled_round(scope, op):
    """``mla_attention_ms_per_round``, ``mla_flash_roofline_pct``,
    ``moe_ms_per_round`` and ``moe_experts_roofline_pct`` read these strings
    out of a trace of ``fit_round_t`` (``layer_metrics/layer_common.py``):
    each is in the name stack of an op of the kind it should hold (the
    interpreted kernel's block slices, the group maximum, the products), on
    the forward and, but for the once-a-round cast, on the backward pass
    (the routed layer's is a ``custom_vjp``'s own function)."""
    lines = [line for line in _model_fit_text("deepseek").splitlines()
             if f"fl_layer::{scope}" in line]
    assert any(re.match(rf"\s*(ROOT )?%{op}", line) for line in lines), (
        scope, len(lines))
    if scope != "shared_cast":
        assert any("transpose(" in line for line in lines), scope
    if scope in ("moe_router", "moe_experts"):  # they nest in the layer's
        assert all("fl_layer::moe/" in line or "fl_layer::moe)" in line
                   for line in lines), scope
