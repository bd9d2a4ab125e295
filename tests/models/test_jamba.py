"""``models/jamba.py`` against the benchmark's plain float32 reference
(``benchmarks/reference/jamba_classifier.py``: jax.numpy, a sequential scan,
dense attention, nothing of the program) at toy widths on seeded weights:
the Mamba mixer alone, the whole forward through both attention forms, and
the gradients of every adapter leaf and the head."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, datagen
from benchmarks.harness.spec import load_module
from fl4health_tpu.clients import engine
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.models.jamba import JambaClassifier, mamba_mixer
from tests.models.remat_probe import pallas_calls

REF = load_module("reference", "jamba_classifier")
NM = load_module("reference", "numerics").FLOAT32
CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 1, "mamba_expand": 2,
    "mamba_d_state": 4, "mamba_dt_rank": 6, "mamba_d_conv": 4,
    "vocab_size": 50, "num_labels": 4, "lora_rank": 2, "lora_alpha": 4,
    "rms_norm_eps": 1e-6, "attn_layer_period": 4, "attn_layer_offset": 2,
}
JOB = {"data": {"seq": 20, "min_len_frac": 0.5}}


def _module(attention_fn=None, remat=False, dtype=jnp.float32):
    c = CFG
    return JambaClassifier(
        vocab_size=c["vocab_size"], n_classes=c["num_labels"],
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], mamba_expand=c["mamba_expand"],
        d_state=c["mamba_d_state"], dt_rank=c["mamba_dt_rank"],
        d_conv=c["mamba_d_conv"], attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"], rms_eps=c["rms_norm_eps"],
        lora_rank=c["lora_rank"], lora_alpha=float(c["lora_alpha"]),
        dtype=dtype, remat=remat, attention_fn=attention_fn)


@pytest.fixture(scope="module")
def seeded():
    """(flat reference weights, the same as the program's tree, tokens)."""
    with jax.default_matmul_precision("highest"):
        flat = datagen.make_weights(REF.param_spec(CFG, JOB), 11)
    # the biases are zeros in the seeded weights: give them values, so that a
    # bias left out on either side shows
    flat = {k: (v + 0.1 * jnp.cos(jnp.arange(v.size, dtype=jnp.float32))
                .reshape(v.shape) if k.endswith("bias") else v)
            for k, v in flat.items()}
    x = np.random.default_rng(0).integers(1, CFG["vocab_size"], (3, 20))
    x[1, 13:] = 0  # a padded tail
    return flat, build.nest(flat), jnp.asarray(x, jnp.int32)


def test_the_programs_tree_is_the_references_param_spec(seeded):
    flat, tree, x = seeded
    init = _module().init(jax.random.PRNGKey(0), x, train=False)["params"]
    got = {k: tuple(v.shape) for k, v in build.flatten(init).items()}
    assert got == {k: tuple(v.shape) for k, v in flat.items()}


def test_mamba_mixer_matches_the_reference(seeded):
    flat, tree, _ = seeded
    s = REF.sizes(CFG, JOB)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 19, s["d"]))
    p = {k[len("layers_0/"):]: v for k, v in flat.items()
         if k.startswith("layers_0/mamba/")}
    got = mamba_mixer(tree["layers_0"]["mamba"], u, _module().dims)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(REF._mamba(p, u, s, NM)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_forward_and_adapter_gradients_match_the_reference(seeded, attention,
                                                           remat):
    flat, tree, x = seeded
    fn = (functools.partial(flash_attention, causal=True, block_q=8,
                            block_k=8) if attention == "flash" else None)
    module = _module(fn, remat)
    y = jnp.asarray([0, 3, 1])

    def ce(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(3), y])

    logits = module.apply({"params": tree}, x)[0]["prediction"]
    want = REF.forward(flat, x, CFG, JOB, NM)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
    # gradients with respect to the per-client leaves alone, the base bound
    # as the engine binds it
    per_client, shared = ptu.split_by_path(tree, module.per_client_param)
    got = jax.grad(lambda p: ce(module.apply(
        {"params": ptu.merge_trees(shared, p)}, x)[0]["prediction"]))(per_client)
    # the engine's path: the shared half prepared (cast, stacked) once, the
    # client's half beside it
    split = module.bind_shared(shared)(per_client, x)
    np.testing.assert_allclose(np.asarray(split[0]["prediction"]),
                               np.asarray(logits), atol=1e-6)
    names = set(build.flatten(per_client))
    ref_grad = jax.grad(lambda p: ce(REF.forward({**flat, **p}, x, CFG, JOB,
                                                 NM)))(
        {k: flat[k] for k in names})
    got = build.flatten(got)
    assert set(got) == names and len(names) == 4 * 12 + 1
    for k in sorted(names):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref_grad[k]),
                                   atol=3e-5, rtol=2e-4, err_msg=k)


@pytest.mark.parametrize("site", ["kept", "bare"])
def test_the_rematerialised_attention_layer_runs_its_flash_forward_once(
        seeded, site, monkeypatch):
    """One attention layer in the four: ONE ``flash_fwd`` in the gradient
    with the kernel's results kept (the site's policy), two under a bare
    ``jax.checkpoint`` (what the site was); the gradients are those without
    remat (and so the reference's: the test above)."""
    _, tree, x = seeded
    if site == "bare":
        monkeypatch.setattr(remat_names, "keep", lambda names: None)
    fn = functools.partial(flash_attention, causal=True, block_q=8, block_k=8)

    def grad(remat):
        module = _module(fn, remat)
        per_client, shared = ptu.split_by_path(tree, module.per_client_param)
        forward = module.bind_shared(shared)
        return jax.grad(lambda p: jnp.sum(jnp.square(
            forward(p, x)[0]["prediction"]))), per_client

    fn_remat, per_client = grad(True)
    jaxpr = jax.make_jaxpr(fn_remat)(per_client).jaxpr
    assert {name: pallas_calls(jaxpr, name)
            for name in ("flash_fwd", "flash_dq", "flash_dkv")} == {
        "flash_fwd": 1 if site == "kept" else 2, "flash_dq": 1,
        "flash_dkv": 1}
    got, want = fn_remat(per_client), grad(False)[0](per_client)
    for k, v in build.flatten(want).items():
        np.testing.assert_allclose(np.asarray(build.flatten(got)[k]),
                                   np.asarray(v), atol=3e-5, rtol=2e-4,
                                   err_msg=k)


@pytest.mark.parametrize("period,offset,n_layers", [(3, 0, 3), (3, 2, 3),
                                                    (2, 1, 5), (14, 7, 2)])
def test_attention_layers_wherever_the_pattern_puts_them(period, offset,
                                                         n_layers):
    """First, last, every other, none: the runs of layers, each one scan,
    follow the reference layer by layer."""
    cfg = dict(CFG, attn_layer_period=period, attn_layer_offset=offset,
               num_hidden_layers=n_layers)
    with jax.default_matmul_precision("highest"):
        flat = datagen.make_weights(REF.param_spec(cfg, JOB), 5)
    x = jnp.asarray(np.random.default_rng(1).integers(1, 50, (2, 12)),
                    jnp.int32)
    module = _module().clone(attn_layer_period=period,
                             attn_layer_offset=offset, n_layers=n_layers,
                             remat=True)
    got = module.apply({"params": build.nest(flat)}, x)[0]["prediction"]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(REF.forward(flat, x, cfg, JOB, NM)),
                               atol=3e-5, rtol=3e-5)


def test_the_module_brings_its_own_split_and_cast(seeded):
    _, tree, _ = seeded
    module = _module(dtype=jnp.bfloat16)
    model = engine.from_flax(module)
    per_client, shared = ptu.split_by_path(tree, model.per_client)
    assert {k.rsplit("/", 1)[-1] for k in build.flatten(per_client)} == {
        "lora_a", "lora_b", "kernel"}
    assert [k for k in build.flatten(per_client) if k.endswith("kernel")] == [
        "score/kernel"]
    cast = build.flatten(module.prepare_shared(shared))
    for k, v in cast.items():
        matrix = k.endswith("/kernel") and "conv1d" not in k
        assert v.dtype == (jnp.bfloat16 if matrix else jnp.float32), k
    assert cast["embed_tokens/embedding"].dtype == jnp.float32


@pytest.mark.parametrize("d_model,attention,want", [
    # 4 heads of 128 over one key/value head: read where the projections
    # wrote them, the shared head by index
    (512, "flash", {"lane_indexed": 1, "transposed": 0}),
    # 4 heads of 8: copied to [B*H, T, 64]
    (32, "flash", {"lane_indexed": 0, "transposed": 1}),
    (512, "dense", {"lane_indexed": 0, "transposed": 0}),
])
def test_build_gauges_count_the_flash_call_sites_by_path(d_model, attention,
                                                         want):
    """One attention layer in the period of four: one call site (the runs of
    Mamba layers hold none), on the path its head width gives."""
    fn = (functools.partial(flash_attention, causal=True, block_q=8,
                            block_k=8) if attention == "flash" else None)
    gauges = _module(fn).clone(d_model=d_model).build_gauges((2, 20), 4)
    # a causal call says how a head walks its live range (PR 44): T 20 is
    # three blocks of 8, the diagonal's three tiles and the three under it,
    # the two digits of a query block's interior run on every one of three
    walk = ({"flash_tiles_edge": 3, "flash_tiles_interior": 3,
             "flash_cond_steps": 9} if attention == "flash" else {})
    assert gauges == {**{f"flash_calls_{k}": v for k, v in want.items()},
                      **walk, "remat_saved_names": 0,
                      "remat_saved_bytes_per_layer": 0}


@pytest.mark.parametrize("d_model,attention,names,per_client", [
    # lane-indexed: out [B, Tp, 4 * 128] and the statistic [B, 4, Tp],
    # 20 positions padded to the blocks of 8
    (512, "flash", 2, 2 * 24 * 512 * 4 + 2 * 4 * 24 * 4),
    # transposed: out [B * 4, Tp, 64] (a head of 8 padded to 64 lanes)
    (32, "flash", 2, 8 * 24 * 64 * 4 + 8 * 24 * 4),
    # the dense form names nothing: the whole layer is recomputed
    (32, "dense", 0, 0),
])
def test_build_gauges_say_what_a_rematerialised_layer_keeps(
        d_model, attention, names, per_client):
    fn = (functools.partial(flash_attention, causal=True, block_q=8,
                            block_k=8) if attention == "flash" else None)
    gauges = _module(fn, remat=True).clone(d_model=d_model).build_gauges(
        (2, 20), 4)
    assert gauges["remat_saved_names"] == names
    assert gauges["remat_saved_bytes_per_layer"] == 4 * per_client


def test_bfloat16_compute_stays_near_float32(seeded):
    flat, tree, x = seeded
    got = _module(dtype=jnp.bfloat16).apply({"params": tree}, x)[0]["prediction"]
    want = REF.forward(flat, x, CFG, JOB, NM)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.15


def test_every_write_of_the_bases_stack_carries_the_cast_scope():
    """Compiled for a described v5e (nothing runs): the fusions that write the
    cast base into its stacks all have ``fl_layer::shared_cast`` in their
    name stack, which is all a trace can give the cast's time to. XLA's own
    split of a concatenate names one update in seven."""
    import os
    import re

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  whatever the plugin raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    module = JambaClassifier(
        vocab_size=512, n_classes=4, d_model=256, n_layers=4, d_ff=512,
        n_heads=2, n_kv_heads=1, dt_rank=16, attn_layer_period=4,
        attn_layer_offset=3, lora_rank=2, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 128), jnp.int32, sharding=chip)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.ones((2, 128), jnp.int32))["params"])
    tree = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    per_client, shared = ptu.split_by_path(tree, module.per_client_param)

    def logits(shared, per_client, x):
        return module.bind_shared(shared)(per_client, x)[0]["prediction"]

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(logits).lower(shared, per_client, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    writes = [line for line in lines[start:]
              if re.match(r"\s+%\S*dynamic-update-slice\S* = bf16\[3,", line)]
    # three Mamba layers in the run: in/x/dt/out_proj and the MLP's three
    assert len(writes) >= 3 * 7
    assert all("fl_layer::shared_cast" in line for line in writes)
