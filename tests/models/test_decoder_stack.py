"""``decoder_common.DecoderStack``: the ONE stack the decoder families run
on. What a family declares is a table (its layers' kinds, the longest unit
that repeats, the final norm's leaf, the embedding's scale, what a remat site
keeps, which ``kernel``s stay float32, a block and its spec); the stack owns
the leaves, the forward over the runs, the split of the parameters and the
base's cast. Here: each family's rows of the table, and a fifth family
declared inside the test going through the same stack."""

import dataclasses
import inspect
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build
from fl4health_tpu.clients import engine
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.afmoe import FULL as F
from fl4health_tpu.models.afmoe import SLIDING as S
from fl4health_tpu.models.afmoe import AfmoeClassifier
from fl4health_tpu.models.deepseek import DeepseekV2Classifier
from fl4health_tpu.models.jamba import JambaClassifier
from fl4health_tpu.models.nemotron_h import NemotronHClassifier
from tests.models.remat_probe import eqns

SIZE = dict(vocab_size=50, n_classes=4)
# each family at its cell's pattern (``benchmarks/configs/*.json``) with the
# runs ``PERF.md`` gives for it, and the parents of the ``kernel``s its base
# keeps in float32
FAMILIES = {
    "jamba": (JambaClassifier, dict(n_layers=14, attn_layer_period=14,
                                    attn_layer_offset=7),
              [list(range(7)), [7], list(range(8, 14))], {"conv1d"}),
    "deepseek": (DeepseekV2Classifier, dict(n_layers=5, first_k_dense=1),
                 [[0], [1, 2, 3, 4]], {"gate"}),
    "nemotron_h": (NemotronHClassifier, dict(pattern="MEMEMEM*EME"),
                   [[(0, 1), (2, 3), (4, 5)], [(6,)], [(7,)], [(8,)], [(9,)],
                    [(10,)]], {"gate", "conv1d"}),
    "afmoe": (AfmoeClassifier, dict(layer_types=(S, S, S, F) * 2,
                                    num_dense_layers=2),
              [[0, 1], [2], [3], [4, 5, 6], [7]], {"router"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_at_its_cells_pattern_has_the_runs_the_records_give(family):
    cls, pattern, runs, _ = FAMILIES[family]
    module = cls(**SIZE, **pattern)
    assert module.runs() == runs


@pytest.mark.parametrize("kinds, max_unit, want", [
    ("AABAA", 1, [[0, 1], [2], [3, 4]]),
    ("ABAB", 1, [[0], [1], [2], [3]]),
    ("ABAB", 2, [[(0, 1), (2, 3)]]),
    # a unit has to repeat to be one: a lone pair is two runs
    ("ABC", 2, [[(0,)], [(1,)], [(2,)]]),
    ("ABCABCD", 3, [[(0, 1, 2), (3, 4, 5)], [(6,)]]),
    # the unit that covers most wins: four singles over one pair twice
    ("AAAAB", 2, [[(0,), (1,), (2,), (3,)], [(4,)]]),
    ([(S, False), (S, True), (S, True)], 1, [[0], [1, 2]]),
    ("", 1, []),
])
def test_pattern_runs_cuts_kinds_into_units_that_repeat(kinds, max_unit, want):
    runs = common.pattern_runs(kinds, max_unit)
    assert runs == want
    flat = [i for run in runs for unit in run
            for i in (unit if max_unit > 1 else (unit,))]
    assert flat == list(range(len(kinds)))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_kernels_left_in_float32_are_the_familys_row(family):
    """``prepare_shared`` casts every ``kernel`` of the base to the compute
    type but those under the names the family lists; nothing else is cast."""
    cls, _, _, parents = FAMILIES[family]
    module = cls(**SIZE, lora_rank=2, dtype=jnp.bfloat16)
    assert set(module.float32_kernels) == parents
    x = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
    per_client, shared = ptu.split_by_path(params, module.per_client_param)
    prepared = build.flatten(jax.eval_shape(module.prepare_shared, shared))
    kernels = {k for k in prepared if k.endswith("/kernel")}
    uncast = {k for k in kernels if prepared[k].dtype == jnp.float32}
    assert {k.split("/")[-2] for k in uncast} == parents
    assert all(prepared[k].dtype == jnp.bfloat16 for k in kernels - uncast)
    assert all(v.dtype == jnp.float32 for k, v in prepared.items()
               if k not in kernels)
    # the layers went into their runs' stacks, the adapters stayed behind
    assert all(k.startswith(("runs/", "embed_tokens/", module.final_norm))
               for k in prepared)
    assert {k.rsplit("/", 1)[-1] for k in build.flatten(per_client)} == {
        "lora_a", "lora_b", "kernel"}


# -- a fifth family: its dims, its block, its spec and the table's rows -----
@dataclasses.dataclass(frozen=True)
class ToyDims:
    rms_eps: float
    lora_scale: float
    dtype: Any


def toy_block(p, h, pad_mask, width, dims):
    del pad_mask, width  # the kinds differ in width, which the leaves carry
    u = common.rms_norm(h, p["norm"]["scale"], dims.rms_eps)
    return h + common.swiglu(p["mlp"], u, dims)


class ToyClassifier(common.DecoderStack):
    vocab_size: int
    n_classes: int
    widths: tuple = (16, 16, 24, 16)  # a layer's kind is its SwiGLU's width
    d_model: int = 8
    rms_eps: float = 1e-6
    lora_rank: int = 2
    dtype: Any = jnp.float32
    remat: bool = False

    final_norm = "ln_f"
    block = staticmethod(toy_block)

    @property
    def dims(self):
        return ToyDims(self.rms_eps, 16.0 / self.lora_rank, self.dtype)

    def kinds(self):
        return self.widths

    def spec(self, width):
        d = self.d_model
        return (("norm", common.norm_spec(d)),
                ("mlp", common.swiglu_spec(d, width, self.lora_rank)))


def test_a_family_costs_under_forty_lines():
    lines = sum(len(inspect.getsource(o).splitlines())
                for o in (ToyDims, toy_block, ToyClassifier))
    assert lines < 40, lines


@pytest.fixture(scope="module")
def toy():
    module = ToyClassifier(**SIZE)
    x = np.random.default_rng(0).integers(1, 50, (3, 12))
    x[1, 7:] = 0  # a padded tail
    x = jnp.asarray(x, jnp.int32)
    params = module.init(jax.random.PRNGKey(0), x, train=False)["params"]
    # lora_b starts at zero: give the adapters something to do
    params = jax.tree_util.tree_map_with_path(
        lambda path, v: v + 0.1 if path[-1].key == "lora_b" else v, params)
    return module, params, x


def test_the_fifth_family_gets_its_leaves_and_runs_from_the_stack(toy):
    module, params, _ = toy
    assert module.runs() == [[0, 1], [2], [3]]
    flat = build.flatten(params)
    assert {k.split("/")[0] for k in flat} == {
        "embed_tokens", "ln_f", "score", *(f"layers_{i}" for i in range(4))}
    assert flat["layers_2/mlp/up_proj/kernel"].shape == (8, 24)
    assert flat["layers_2/mlp/up_proj/lora_b"].shape == (2, 24)
    assert flat["embed_tokens/embedding"].shape == (50, 8)


@pytest.mark.parametrize("remat", [False, True])
def test_the_fifth_familys_bound_forward_is_its_apply(toy, remat):
    _, params, x = toy
    module = ToyClassifier(**SIZE, remat=remat)
    want = module.apply({"params": params}, x)[0]["prediction"]
    per_client, shared = ptu.split_by_path(params, module.per_client_param)
    assert {k.rsplit("/", 1)[-1] for k in build.flatten(per_client)} == {
        "lora_a", "lora_b", "kernel"}
    assert [k for k in build.flatten(per_client) if k.endswith("kernel")] == [
        "score/kernel"]
    got = module.bind_shared(shared)(per_client, x)[0]["prediction"]
    assert got.shape == (3, 4) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        module.bind_shared(shared)(p, x)[0]["prediction"])))(per_client)
    sites = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name in (
        "checkpoint", "remat", "remat2")]
    # one site a layer inside each run's scan body: three runs
    assert len(sites) == (3 if remat else 0)


def test_the_fifth_familys_gradients_are_the_adapters_and_the_heads(toy):
    module, params, x = toy
    per_client, shared = ptu.split_by_path(params, module.per_client_param)
    forward = module.bind_shared(shared)
    y = jnp.asarray([1, 0, 3])

    def ce(p):
        logits = forward(p, x)[0]["prediction"]
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(3), y])

    got = build.flatten(jax.grad(ce)(per_client))
    # three projections in each of four layers, two leaves each, and the head
    assert len(got) == 4 * 3 * 2 + 1 and "score/kernel" in got
    assert all(float(jnp.abs(v).max()) > 0 for v in got.values())
    whole = build.flatten(jax.grad(lambda p: -jnp.mean(jax.nn.log_softmax(
        module.apply({"params": p}, x)[0]["prediction"])[jnp.arange(3), y]))(
            params))
    for k, v in got.items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(whole[k]),
                                   atol=1e-6, err_msg=k)


def test_the_engine_takes_the_fifth_family_by_the_same_seam(toy):
    module, params, x = toy
    model = engine.from_flax(module)
    # a family without gauges of its own brings none
    assert model.bind_shared is not None and model.build_gauges is None
    per_client, shared = ptu.split_by_path(params, model.per_client)
    (got, _), state = model.bind_shared(shared)(per_client, {}, x)
    (want, _), _ = model.apply(params, {}, x)
    assert state == {}
    np.testing.assert_allclose(np.asarray(got["prediction"]),
                               np.asarray(want["prediction"]), atol=1e-6)
