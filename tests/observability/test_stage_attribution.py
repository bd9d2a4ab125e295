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

from fl4health_tpu.compression import CompressionConfig
from fl4health_tpu.observability import stages as stage_attr
from fl4health_tpu.privacy import dpsgd
from fl4health_tpu.resilience import RobustFedAvg
from fl4health_tpu.server.registry import CohortConfig
from tests.observability.round_programs import compiled_texts as _compiled_texts
from tests.observability.round_programs import family_module
from tests.observability.round_programs import mlp_sim as _mlp_sim
from tests.observability.round_programs import token_sim as _token_sim

pytestmark = pytest.mark.roofline


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
        both = {"local_train", "server_update"}
        # the chunk scan holds the evaluation round too
        for text, scopes in ((per_round["fit_round_t"], both),
                             (chunk["fit_chunk_eval"], both | {"evaluate"})):
            assert _scopes(text) == scopes
            assert any("fl_stage::local_train" in line and "transpose(" in line
                       for line in text.splitlines())

    def test_cohort_exchange_is_the_cohort_chunks_own(self):
        """The in-graph gather/scatter of the registry window exists in the
        chunk scan only: the slot programs beside it draw on the host."""
        texts = _compiled_texts(
            _mlp_sim(cohort=CohortConfig(slots=3), mode="chunked"))
        assert _scopes(texts["fit_cohort_chunk"]) == {
            "cohort_exchange", "local_train", "server_update", "evaluate"}
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
        "plain_eval": ("eval_round_t", dict(mode="pipelined")),
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
    "evaluate": "plain_eval",
}


@pytest.mark.parametrize("stage", stage_attr.SPINE_STAGES)
def test_every_spine_stage_names_its_seam_in_the_compiled_program(stage):
    assert stage in _scopes(_seam_text(SEAM_OF[stage]))


@functools.cache
def _model_fit_text(family):
    return _compiled_texts(_token_sim(family_module(family)))["fit_round_t"]


@pytest.mark.parametrize("stage", ["local_train", "server_update"])
@pytest.mark.parametrize("family", ["transformer", "jamba", "deepseek",
                                    "nemotron", "afmoe"])
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


@pytest.mark.parametrize("scope,op,inside", [
    ("ssd_mixer", "dot", None), ("ssd_scan", "exponential", "ssd_mixer"),
    ("attention", "dot", None), ("gqa_flash", "dynamic_slice", "attention"),
    ("moe", "dot_general", None), ("moe_latent", "dot", "moe"),
    ("moe_router", "dot", "moe"), ("moe_experts", "dot_general", "moe"),
    ("shared_experts", "dot", None), ("shared_cast", "convert", None)])
def test_the_hybrid_familys_layer_scopes_reach_the_compiled_round(scope, op,
                                                                  inside):
    """``ssd_mixer_ms_per_round``, ``ssd_scan_ms_per_round`` /
    ``ssd_scan_roofline_pct``, ``gqa_flash_roofline_pct``,
    ``moe_latent_ms_per_round`` and ``routed_experts_roofline_pct`` read
    these strings out of a trace of ``fit_round_t``: each is in the name
    stack of an op of the kind it should hold (the decays' exponentials, the
    interpreted kernel's block slices, the router's logits, the products),
    on the forward and, but for the once-a-round cast, on the backward pass,
    and the inner scopes nest in their outer one."""
    lines = [line for line in _model_fit_text("nemotron").splitlines()
             if f"fl_layer::{scope}" in line]
    assert any(re.match(rf"\s*(ROOT )?%\w*{op}", line) for line in lines), (
        scope, len(lines))
    if scope != "shared_cast":
        assert any("transpose(" in line for line in lines), scope
    if inside:
        assert all(f"fl_layer::{inside}/" in line
                   or f"fl_layer::{inside})" in line for line in lines), scope


@pytest.mark.parametrize("scope,op,inside", [
    ("attention", "dot", None), ("window_flash", "dynamic_slice", "attention"),
    ("gqa_flash", "dynamic_slice", "attention"),
    ("norm", "rsqrt", None),
    ("mlp", "dot", None), ("moe", "dot_general", None),
    ("moe_router", "dot", "moe"), ("moe_experts", "dot_general", "moe"),
    ("shared_experts", "dot", None), ("shared_cast", "convert", None)])
def test_the_window_familys_layer_scopes_reach_the_compiled_round(scope, op,
                                                                  inside):
    """``window_flash_ms_per_round`` / ``window_flash_roofline_pct``,
    ``gqa_flash_roofline_pct`` (the full layers' calls keep that name) read
    these strings out of a trace of
    ``fit_round_t``: each is in the name stack of an op of the kind it should
    hold (the interpreted kernel's block slices, the products), on the forward and, but for the once-a-round cast, on the
    backward pass, and the inner scopes nest in their outer one. The two
    head norms nest in ``attention``."""
    lines = [line for line in _model_fit_text("afmoe").splitlines()
             if f"fl_layer::{scope}" in line]
    assert any(re.match(rf"\s*(ROOT )?%\w*{op}", line) for line in lines), (
        scope, len(lines))
    if scope != "shared_cast":
        assert any("transpose(" in line for line in lines), scope
    if inside:
        assert all(f"fl_layer::{inside}/" in line
                   or f"fl_layer::{inside})" in line for line in lines), scope
    if scope == "norm":
        assert any("fl_layer::attention/" in line for line in lines)
        assert any("fl_layer::attention" not in line for line in lines)
    if scope in ("window_flash", "gqa_flash"):  # a layer has one or the other
        other = {"window_flash": "gqa_flash", "gqa_flash": "window_flash"}
        assert not any(f"fl_layer::{other[scope]}" in line for line in lines)
