"""The client step's parts and passes (observability/stages.py).

The pinned contracts:

- every ``fl_layer::`` scope a model family declares, and the engine's own,
  reaches the op metadata of the COMPILED ``fit_round_t`` (where a profiler
  trace's names come from), and ``fl_stage::evaluate`` that of the evaluation
  program on both drivers, with and without telemetry;
- the scopes are METADATA-ONLY: the lowered StableHLO of the families'
  round programs is text-equal with the scopes on and off;
- the pass is read from JAX's own markers (``jvp(``, ``transpose(``,
  ``rematted_computation``): they are where ``pass_of`` expects them in the
  compiled name stacks of a vmapped, scanned, rematted step and of the flash
  and selective-scan ``custom_vjp`` rules, so a JAX upgrade that renames one
  fails here and does not silently zero a metric;
- nothing is named that nothing reads: every ``LAYER_SCOPES`` name has a
  reader among ``BENCHMARK.json``'s per-layer metrics.
"""

import functools
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.kernels.selective_scan import selective_scan
from fl4health_tpu.observability import (MetricsRegistry, Observability,
                                         Tracer)
from fl4health_tpu.observability import stages
from fl4health_tpu.precision import PrecisionConfig
from fl4health_tpu.server.simulation import EXEC_CHUNKED, EXEC_PIPELINED
from tests.observability.round_programs import (family_module,
                                                lowered_programs,
                                                metadata_in_cache_key,
                                                mlp_sim, token_sim)

pytestmark = pytest.mark.roofline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the cells' jobs at toy size: adapters, remat and the flash calls in all
# three, so every scope of a family has an op
FLASH = functools.partial(flash_attention, block_q=8, block_k=8)
FAMILIES = {
    "transformer": dict(lora_rank=2, remat=True, dtype=jnp.bfloat16,
                        attention_fn=FLASH),
    "jamba": dict(remat=True, dtype=jnp.bfloat16,
                  attention_fn=functools.partial(FLASH, causal=True)),
    "deepseek": {},
    "nemotron": {},
    "afmoe": {},
}
DECLARED = {
    "transformer": ("embed", "attention", "mlp", "norm", "lora", "head",
                    "optimizer", "param_cast"),
    "jamba": ("embed", "attention", "mamba_mixer", "ssm_scan", "mlp", "norm",
              "lora", "head", "optimizer", "shared_cast"),
    "deepseek": ("embed", "mla_attention", "mla_flash", "mlp", "moe",
                 "moe_router", "moe_experts", "shared_experts", "norm",
                 "lora", "head", "optimizer", "shared_cast"),
    "nemotron": ("embed", "attention", "gqa_flash", "ssd_mixer", "ssd_scan",
                 "moe", "moe_router", "moe_experts", "moe_latent",
                 "shared_experts", "norm", "lora", "head", "optimizer",
                 "shared_cast"),
    "afmoe": ("embed", "attention", "gqa_flash", "window_flash",
              "mlp", "moe", "moe_router", "moe_experts", "shared_experts",
              "norm", "lora", "head", "optimizer", "shared_cast"),
}
# a frozen base's embedding has no gradient; XLA:CPU folds the cast's
# transpose (a gradient back to float32) into the product that feeds it
FORWARD_ONLY = {("jamba", "embed"), ("deepseek", "embed"),
                ("nemotron", "embed"), ("afmoe", "embed"),
                ("transformer", "param_cast")}


def _op_names(lowered):
    """The complete name stacks of the compiled program's ops: those that
    start at the program. A stack that starts further in is left out here.
    Some are the ops inside a reduction's own computation, which are no ops
    of a trace. Others ARE: the routed layer's ``custom_vmap`` rule
    (``models/routed.py _fold_clients``) is traced in a name stack of its
    own, so its sort, scatter-adds and tile loops keep ``fl_layer::moe`` and
    lose ``fl_stage::local_train`` (PERF.md section 7: the expert cell's
    ``unstaged_device_pct``)."""
    with metadata_in_cache_key():
        text = lowered.compile().as_text()
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(")]


def _family_sim(family):
    return token_sim(family_module(family, **FAMILIES[family]))


@functools.cache
def _family_names(family):
    """program -> op name stacks of the family's two round programs."""
    return {name: _op_names(lowered) for name, lowered in
            lowered_programs(_family_sim(family)).items()}


# -- (a) the scopes reach the compiled programs ---------------------------
def test_every_layer_scope_is_declared_by_a_family_or_the_engine():
    declared = {s for scopes in DECLARED.values() for s in scopes}
    assert declared == set(stages.LAYER_SCOPES)


@pytest.mark.parametrize("family,scope", [
    (family, scope) for family, scopes in DECLARED.items() for scope in scopes])
def test_a_familys_scopes_reach_the_compiled_fit_round(family, scope):
    """Each is in the name stack of an op of ``fit_round_t`` under
    ``local_train`` (but the shared base's cast, which is bound outside the
    client vmap), forward and, where it is differentiated, backward."""
    names = [n for n in _family_names(family)["fit_round_t"]
             if scope in stages.layers_of(n)]
    assert names, (family, scope)
    if scope == "shared_cast":
        assert {stages.stage_of(n) for n in names} == {None}
        return
    assert {stages.stage_of(n) for n in names} == {"local_train"}
    passes = {stages.pass_of(n) for n in names}
    if scope == "optimizer":
        assert passes == {"update"}
    elif (family, scope) in FORWARD_ONLY:
        assert "forward" in passes and "backward" not in passes
    else:
        assert {"forward", "backward"} <= passes, (scope, passes)


@pytest.mark.parametrize("family", list(DECLARED))
def test_a_familys_forward_scopes_reach_the_evaluation_program(family):
    """The evaluation forwards carry the parts' names under
    ``fl_stage::evaluate``: the layer x pass table's ``evaluate`` column."""
    names = _family_names(family)["eval_round_t"]
    under = {layer for n in names if stages.stage_of(n) == "evaluate"
             for layer in stages.layers_of(n)}
    assert under >= set(DECLARED[family]) - {"optimizer", "shared_cast"}
    assert not any(stages.pass_of(n) for n in names)


def test_the_precision_policys_cast_carries_param_cast():
    """A model without a ``dtype`` of its own is cast by
    ``precision/policy.py cast_model_def``: the cast and its transpose."""
    sim = mlp_sim(mode="pipelined", precision=PrecisionConfig("bf16"))
    lowered = lowered_programs(sim)["fit_round_t"]
    names = [n for n in _op_names(lowered)
             if "param_cast" in stages.layers_of(n)]
    assert "forward" in {stages.pass_of(n) for n in names}
    # the transpose is lowered under the scope too (XLA:CPU then folds it
    # into the product before it, see FORWARD_ONLY)
    assert ('"transpose(jvp(fl_layer::param_cast))/convert_element_type"'
            in lowered.as_text(debug_info=True))


@pytest.mark.parametrize("telemetry", [True, False],
                         ids=["telemetry", "no_telemetry"])
@pytest.mark.parametrize("mode,driver,program", [
    ("pipelined", EXEC_PIPELINED, "eval_round"),
    ("chunked", EXEC_CHUNKED, "fit_chunk_eval")])
def test_evaluate_names_the_evaluation_rounds_client_part(mode, driver,
                                                          program, telemetry):
    """``eval_ms_per_round`` reads this string: ``eval_round`` and
    ``eval_round_t`` carry it on the per-round driver, the chunk scan holds
    the same round function."""
    sim = mlp_sim(mode=mode, observability=Observability(
        enabled=True, telemetry=telemetry, tracer=Tracer(),
        registry=MetricsRegistry()))
    if driver == EXEC_PIPELINED and telemetry:
        program += "_t"
    names = _op_names(lowered_programs(sim, driver, 2)[program])
    evaluate = [n for n in names if stages.stage_of(n) == "evaluate"]
    assert any("dot_general" in n for n in evaluate)
    # the training step's ops stay local_train's
    assert not any(stages.pass_of(n) for n in evaluate)


# -- (b) metadata only ----------------------------------------------------
@pytest.mark.parametrize("family", list(DECLARED))
def test_the_round_programs_are_text_equal_without_the_scopes(family):
    """Named scopes add, move and re-fuse no op: the StableHLO the two
    round programs lower to is the same text with ``stages.disabled()``."""
    scoped = lowered_programs(_family_sim(family))
    with stages.disabled():
        bare = lowered_programs(_family_sim(family))
    assert set(scoped) == set(bare) == {"fit_round_t", "eval_round_t"}
    for name in scoped:
        assert scoped[name].as_text() == bare[name].as_text(), name
        assert "fl_layer::" in scoped[name].as_text(debug_info=True)
        assert "fl_layer::" not in bare[name].as_text(debug_info=True)
        assert "fl_stage::" not in bare[name].as_text(debug_info=True)


# -- (c) the pass, from JAX's own markers ---------------------------------
class _Block(nn.Module):
    @nn.compact
    def __call__(self, h):
        return h + nn.Dense(8)(jnp.tanh(nn.Dense(8)(h)))


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        for i in range(2):
            x = nn.remat(_Block)(name=f"block_{i}")(x)
        return x.sum()


def _losses(kind):
    """(params, loss(params, x), x) of a toy step: x is one step's batch."""
    x = jnp.ones((4, 8))
    if kind == "nn.remat":
        net = _Net()
        return net.init(jax.random.PRNGKey(0), x), net.apply, x
    if kind == "jax.checkpoint":
        def loss(p, x):
            body = jax.checkpoint(lambda h, w: (h + jnp.tanh(h @ w), None))
            return jax.lax.scan(body, x, p["w"])[0].sum()
        return {"w": jnp.ones((2, 8, 8))}, loss, x
    if kind == "flash":
        def loss(p, x):
            q = (x[None] * p["w"]).reshape(1, 4, 1, 8)
            return FLASH(q, q, q, pad_mask=jnp.ones((1, 4)), block_q=4,
                         block_k=4).sum()
        return {"w": jnp.ones((8,))}, loss, x
    if kind == "selective_scan":
        def loss(p, x):
            u = (x * p["w"])[None]  # [1, T, channels]
            b = jnp.ones((1, 4, 2))
            return selective_scan(u, jnp.ones_like(u), -jnp.ones((8, 2)), b,
                                  b, jnp.ones((8,)), u).sum()
        return {"w": jnp.ones((8,))}, loss, x
    raise KeyError(kind)


@functools.cache
def _toy_step_names(kind):
    """Name stacks of the compiled toy client step: three clients under
    ``vmap``, two local steps under ``lax.scan``, the engine's stage and
    optimizer scopes around the same places."""
    params, loss, x = _losses(kind)

    def client(p, xs):
        def step(p, x):
            with stages.stage("local_train"):
                _, grads = jax.value_and_grad(loss)(p, x)
                with stages.layer("optimizer"):
                    p = jax.tree_util.tree_map(
                        lambda a, g: a - 0.1 * g, p, grads)
            return p, None

        return jax.lax.scan(step, p, xs)[0]

    stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a] * 3), params)
    xs = jnp.broadcast_to(x, (3, 2, *x.shape))
    return _op_names(jax.jit(jax.vmap(client)).lower(stacked, xs))


@pytest.mark.parametrize("kind", ["jax.checkpoint", "nn.remat"])
def test_pass_of_finds_the_four_passes_of_a_rematted_step(kind):
    names = _toy_step_names(kind)
    by_pass = {p: [n for n in names if stages.pass_of(n) == p]
               for p in stages.PASSES}
    assert all(by_pass.values()), {p: len(v) for p, v in by_pass.items()}
    # the nonlinearity runs in the forward and again in the recompute, and
    # its derivative's product on the way back
    assert any(n.endswith("tanh") for n in by_pass["forward"])
    assert any(n.endswith("tanh") for n in by_pass["recompute"])
    assert not any(n.endswith("tanh") for n in by_pass["backward"])
    assert any("dot_general" in n for n in by_pass["backward"])
    assert all("fl_layer::optimizer" in n for n in by_pass["update"]
               if n.endswith("sub"))
    # every marker where pass_of expects it
    assert all("transpose(" in n for n in by_pass["recompute"])
    assert not any("rematted_computation" in n for n in by_pass["backward"])
    assert not any("transpose(" in n or "rematted_computation" in n
                   for n in by_pass["forward"])
    assert not any("jvp(" in n for n in by_pass["update"])


@pytest.mark.parametrize("kind,forward,backward", [
    ("flash", ("flash_fwd",), ("flash_dq", "flash_dkv")),
    ("selective_scan", ("ssm_scan_fwd",), ("ssm_scan_bwd",))])
def test_pass_of_gives_a_custom_vjps_rules_their_pass(kind, forward,
                                                      backward):
    """A ``custom_vjp``'s backward function runs where the cotangents do:
    its kernels' ops are ``backward``, its forward rule's ``forward``."""
    names = _toy_step_names(kind)
    for kernel, want in [(k, "forward") for k in forward] + [
            (k, "backward") for k in backward]:
        ops = [n for n in names if kernel in n]
        assert ops, kernel
        assert {stages.pass_of(n) for n in ops} == {want}, kernel


def test_pass_of_is_none_outside_local_train():
    assert stages.pass_of("jit(eval_round)/fl_stage::evaluate/jvp(f)/x") is None
    assert stages.pass_of("jit(f)/transpose(jvp(f))/mul") is None
    assert stages.pass_of(None) is None


# -- (d) nothing is named that nothing reads ------------------------------
def _scopes_the_benchmark_reads():
    """The ``fl_layer::`` names the readers of ``BENCHMARK.json``'s per-layer
    metrics hand to ``layer_common`` (``ms_per_round`` / ``seconds``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        metrics = json.load(f)["per_layer"]
    read = set()
    for m in metrics:
        path = os.path.join(REPO, "benchmarks", "layer_metrics",
                            m["name"] + ".py")
        with open(path) as f:
            source = f.read()
        if '"layer_common"' in source:
            read.update(re.findall(
                r'\.(?:ms_per_round|seconds)\(ctx,\s*"(\w+)"\)', source))
    return read


def test_every_layer_scope_has_a_reader_in_the_benchmark():
    assert set(stages.LAYER_SCOPES) <= _scopes_the_benchmark_reads()


def test_no_layer_scope_is_spelled_outside_the_vocabulary():
    """Every ``fl_layer::`` scope of the program goes through
    ``stages.layer``: no bare ``jax.named_scope`` literal, and every name a
    call site passes is in ``LAYER_SCOPES``."""
    package = os.path.join(REPO, "fl4health_tpu")
    passed = set()
    for folder, _, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                source = f.read()
            if name != "stages.py":
                assert not re.search(r'named_scope\(\s*["\']fl_(layer|stage)::',
                                     source), name
            passed.update(re.findall(
                r'\b(?:part|layer)\(\s*"(\w+)"\s*\)', source))
    assert passed == set(stages.LAYER_SCOPES)
