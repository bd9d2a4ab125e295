"""``models/afmoe.py`` against the benchmark's plain float32 reference
(``benchmarks/reference/afmoe_classifier.py``: jax.numpy, dense attention by
query blocks with the window as a mask, every held expert over every token
under a mask, nothing of the program) at toy widths on seeded weights: the
tree, the pattern's runs, rotary positions on the sliding layers only, the
window in the dense and the flash form, the gate, the forward and every
adapter gradient, the shares of a layer that add up to the uncut layer, the
split and the gauges. Every tolerance says what it is for and what it
refuses."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, datagen
from benchmarks.harness.spec import load_module
from fl4health_tpu.clients import engine
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.models import afmoe
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models import deepseek, routed
from tests.models.remat_probe import eqns

REF = load_module("reference", "afmoe_classifier")
NM = load_module("reference", "numerics").FLOAT32
S, F = afmoe.SLIDING, afmoe.FULL
# the published structure at toy widths: a period SSSF and a half, two
# leading dense layers, 4 query heads of 128 lanes over 2 key/value heads
# (the flash calls' grouped addressing), a window of 6 under 20 positions, 8
# of 40 experts held from the sixth, 6 a token, renormalised and scaled
CFG = {
    "hidden_size": 32, "num_hidden_layers": 6,
    "layer_types": [S, S, S, F, S, S, S, F], "num_dense_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "sliding_window": 6, "rope_theta": 10000, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_shared_experts": 1,
    "num_experts": 8, "first_expert_held": 5, "router_width": 40,
    "num_experts_per_tok": 6, "route_scale": 2.826, "mup_enabled": True,
    "vocab_size": 50, "num_labels": 4, "lora_rank": 2, "lora_alpha": 4,
    "rms_norm_eps": 1e-5,
}
JOB = {"data": {"seq": 20, "min_len_frac": 0.5}}
FLASH = functools.partial(flash_attention, causal=True, block_q=8, block_k=8)


def _module(cfg=CFG, attention_fn=None, remat=False, dtype=jnp.float32):
    c = cfg
    return afmoe.AfmoeClassifier(
        vocab_size=c["vocab_size"], n_classes=c["num_labels"],
        layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        num_dense_layers=c["num_dense_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_routed_experts=c["router_width"], experts_held=c["num_experts"],
        first_expert_held=c["first_expert_held"],
        n_shared_experts=c["num_shared_experts"],
        top_k=c["num_experts_per_tok"], route_scale=float(c["route_scale"]),
        sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
        lora_rank=c["lora_rank"],
        lora_alpha=float(c["lora_alpha"]), dtype=dtype, remat=remat,
        attention_fn=attention_fn)


def _weights(cfg, seed):
    with jax.default_matmul_precision("highest"):
        return datagen.make_weights(REF.param_spec(cfg, JOB), seed)


def _layer_leaves(flat, i):
    prefix = f"layers_{i}/"
    return {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}


@pytest.fixture(scope="module")
def seeded():
    """(flat reference weights, the same as the program's tree, tokens)."""
    flat = _weights(CFG, 11)
    x = np.random.default_rng(0).integers(1, CFG["vocab_size"], (3, 20))
    x[1, 13:] = 0  # a padded tail
    return flat, build.nest(flat), jnp.asarray(x, jnp.int32)


# -- structure ---------------------------------------------------------------
def test_the_programs_tree_is_the_references_param_spec(seeded):
    flat, _, x = seeded
    init = _module().init(jax.random.PRNGKey(0), x, train=False)["params"]
    got = {k: tuple(v.shape) for k, v in build.flatten(init).items()}
    assert got == {k: tuple(v.shape) for k, v in flat.items()}
    # four norms a layer, five projections and two head norms in attention
    assert {k.split("/")[1] for k in got if k.startswith("layers_0/")} == {
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm", "self_attn", "mlp"}
    assert {k.split("/")[2] for k in got
            if k.startswith("layers_0/self_attn/")} == {
        "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "q_norm",
        "k_norm"}
    assert got["layers_0/self_attn/q_norm/scale"] == (128,)
    assert got["layers_0/self_attn/gate_proj/kernel"] == (32, 4 * 128)
    assert got["layers_1/mlp/up_proj/kernel"] == (32, 48)  # a dense layer
    assert got["layers_2/mlp/router/kernel"] == (32, 40)
    assert got["layers_2/mlp/expert_bias"] == (40,)
    assert got["layers_2/mlp/experts_7/down_proj/kernel"] == (24, 32)
    # experts, router and norms carry no adapter; the shared expert does
    assert not any(("experts_" in k.replace("shared_experts", "")
                    or "/router/" in k or "norm" in k) and "lora" in k
                   for k in got)
    assert "layers_2/mlp/shared_experts/up_proj/lora_b" in got
    assert "layers_0/self_attn/gate_proj/lora_a" in got


@pytest.mark.parametrize("kinds,dense,want", [
    # the cell's cut: two periods, two leading dense layers
    ([S, S, S, F, S, S, S, F], 2, [[0, 1], [2], [3], [4, 5, 6], [7]]),
    ([S, S, S, F], 0, [[0, 1, 2], [3]]),
    ([S, S, S, F], 4, [[0, 1, 2], [3]]),
    ([S, S, S, F, S], 3, [[0, 1, 2], [3], [4]]),
    ([F], 0, [[0]]),
])
def test_layers_alike_in_kind_and_feed_forward_scan_together(kinds, dense,
                                                             want):
    """A run is one ``lax.scan``: layers that follow one another with the
    same kind of attention AND the same feed-forward; every layer is in
    exactly one run, in order."""
    module = _module(dict(CFG, layer_types=kinds, num_dense_layers=dense,
                          num_hidden_layers=len(kinds)))
    runs = module.runs()
    assert runs == want
    assert [i for run in runs for i in run] == list(range(len(kinds)))
    for run in runs:
        assert len({(kinds[i], i >= dense) for i in run}) == 1


# -- attention -----------------------------------------------------------------
def _attention_inputs(seeded, layer):
    flat, tree, _ = seeded
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 32))
    mask = (jnp.arange(20)[None, :] < jnp.asarray([[20], [13]])).astype(
        jnp.float32)
    return (_layer_leaves(flat, layer), tree[f"layers_{layer}"]["self_attn"],
            u, mask)


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("layer", [0, 3])
def test_gated_attention_matches_the_reference(seeded, layer, attention):
    """A sliding layer (0: window 6, rotary) and a full one (3: neither),
    through the dense form and the flash calls. 2e-5 is float32 summation
    order; a window off by one, positions on the full layer or a dropped
    gate are 1e-2 and more (the tests below)."""
    p, tree_p, u, mask = _attention_inputs(seeded, layer)
    module = _module(attention_fn=FLASH if attention == "flash" else None)
    dims, kind = module.dims, CFG["layer_types"][layer]
    rope = common.rope_tables(20, 128, 1e4) if kind == S else None
    with jax.default_matmul_precision("highest"):
        got = afmoe.gated_attention(tree_p, u, mask,
                                    6 if kind == S else None, rope, dims)
        want = REF._attention(p, u, mask, kind, REF.sizes(CFG, JOB), NM)
    real = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose(np.asarray(got) * real,
                               np.asarray(want) * real, atol=2e-5)


@pytest.mark.parametrize("fault", [
    "a window one position short", "a window one position long",
    "positions on a full layer", "no positions on a sliding layer",
    "the gate dropped", "no head norm"])
def test_what_the_attention_tolerance_refuses(seeded, fault):
    layer = 3 if fault == "positions on a full layer" else 0
    p, tree_p, u, mask = _attention_inputs(seeded, layer)
    dims, kind = _module().dims, CFG["layer_types"][layer]
    rope = common.rope_tables(20, 128, 1e4)
    window = None if kind == F else 6
    if fault == "a window one position short":
        window = 5
    elif fault == "a window one position long":
        window = 7
    elif fault == "no positions on a sliding layer":
        rope = None
    elif fault != "positions on a full layer" and kind == F:
        rope = None
    if fault == "the gate dropped":
        # g = 0 everywhere: sigmoid(g) is 0.5, and twice the (linear)
        # output projection of it is the attention with no gate
        tree_p = dict(tree_p, gate_proj={"kernel": jnp.zeros((32, 512))})
    if fault == "no head norm":
        tree_p = dict(tree_p, q_norm={"scale": 3.0 * jnp.ones(128)})
    with jax.default_matmul_precision("highest"):
        got = afmoe.gated_attention(tree_p, u, mask, window, rope, dims)
        if fault == "the gate dropped":
            got = got * 2.0
        want = REF._attention(p, u, mask, kind, REF.sizes(CFG, JOB), NM)
    real = np.asarray(mask)[:, :, None]
    err = float(np.abs((np.asarray(got) - np.asarray(want)) * real).max())
    assert err > 1e-2, (fault, err)


def test_rotary_positions_on_the_sliding_layers_only(seeded):
    """The traced layer: a sliding layer's attention holds the cos / sin of
    the positions, a full layer's holds none."""
    _, tree, _ = seeded
    dims = _module().dims
    h = jax.ShapeDtypeStruct((1, 20, 32), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, 20), jnp.float32)

    def trig(kind, i):
        jaxpr = jax.make_jaxpr(lambda p, h, m: afmoe.layer(
            p, h, m, kind, False, dims))(tree[f"layers_{i}"], h, mask).jaxpr
        return sum(e.primitive.name in ("cos", "sin") for e in eqns(jaxpr))

    assert trig(S, 0) == 2 and trig(F, 1) == 0


def test_plain_rotary_tables_are_theta_alone():
    """``decoder_common.rope_tables`` is the plain rotary embedding, and
    ``deepseek.rope_tables`` at factor 1 is it exactly: theta's own
    frequencies, times 1.0."""
    cos, sin = common.rope_tables(20, 128, 1e4)
    for a, b in zip((cos, sin), deepseek.rope_tables(
            20, 128, deepseek.RopeScaling(theta=1e4))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    inv = np.asarray([1e4 ** (-2.0 * i / 128) for i in range(64)], np.float32)
    ang = np.arange(20, dtype=np.float32)[:, None] * inv[None, :]
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(jnp.cos(ang)))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(jnp.sin(ang)))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 2, 128))
    np.testing.assert_allclose(
        np.asarray(common.apply_rope(x, cos, sin)),
        np.asarray(REF._rotary(x, 1e4)), atol=1e-6)


# -- the router ----------------------------------------------------------------
def test_router_picks_what_the_reference_picks(seeded):
    """The sigmoid rule at this family's numbers, with the seeded, NON-ZERO
    ``expert_bias``: the picks follow ``s + b``, the weights ``s`` alone,
    renormalised over the chosen and scaled by 2.826."""
    flat, tree, _ = seeded
    mlp = tree["layers_2"]["mlp"]
    assert float(jnp.abs(mlp["expert_bias"]).max()) > 0.01
    u = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    idx, w = routed.sigmoid_route(
        {"kernel": mlp["router"]["kernel"],
         "e_score_correction_bias": mlp["expert_bias"]}, u, 6, 2.826)
    want = np.asarray(REF.route(_layer_leaves(flat, 2), u,
                                REF.sizes(CFG, JOB)))
    assert (np.count_nonzero(want, axis=1) == 6).all()
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    # float32 on both sides: 1e-5 is rounding; a bfloat16 router reads 4e-3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.826, rtol=1e-5)


# -- the whole model ---------------------------------------------------------
@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_forward_and_adapter_gradients_match_the_reference(seeded, attention,
                                                           remat):
    """Logits, loss and every adapter's gradient. 3e-5 absolute / 2e-4
    relative is float32 summation order through six layers (the same the
    other decoder families are held to)."""
    flat, tree, x = seeded
    module = _module(attention_fn=FLASH if attention == "flash" else None,
                     remat=remat)
    y = jnp.asarray([0, 3, 1])

    def ce(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(3), y])

    with jax.default_matmul_precision("highest"):
        logits = module.apply({"params": tree}, x)[0]["prediction"]
        want = REF.forward(flat, x, CFG, JOB, NM)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        assert abs(float(ce(logits)) - float(ce(want))) < 1e-5
        per_client, shared = ptu.split_by_path(tree, module.per_client_param)
        # the engine's path: the shared half prepared (cast, stacked) once,
        # the client's half beside it
        forward = module.bind_shared(shared)
        np.testing.assert_allclose(
            np.asarray(forward(per_client, x)[0]["prediction"]),
            np.asarray(logits), atol=1e-6)
        got = build.flatten(jax.grad(
            lambda p: ce(forward(p, x)[0]["prediction"]))(per_client))
        names = set(got)
        ref_grad = jax.grad(lambda p: ce(REF.forward(
            {**flat, **p}, x, CFG, JOB, NM)))({k: flat[k] for k in names})
    # five attention projections in each of six layers, three matrices of the
    # two dense layers and of the four shared experts, two leaves each, and
    # the head
    assert len(names) == 2 * (6 * 5 + 6 * 3) + 1
    for k in sorted(names):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref_grad[k]),
                                   atol=3e-5, rtol=2e-4, err_msg=k)
    # the router's input trains what lies before it
    assert float(jnp.abs(got["layers_1/mlp/down_proj/lora_b"]).max()) > 0


def test_the_embedding_is_scaled_by_the_square_root_of_the_width(seeded):
    """The program's one form (``mup_enabled``): the reference WITHOUT the
    scale over an embedding multiplied beforehand gives the program's
    logits, and over the embedding as seeded it does not."""
    flat, tree, x = seeded
    emb = "embed_tokens/embedding"
    with jax.default_matmul_precision("highest"):
        scaled = _module().apply({"params": tree}, x)[0]["prediction"]
        off = dict(CFG, mup_enabled=False)
        want = REF.forward(
            {**flat, emb: flat[emb] * math.sqrt(CFG["hidden_size"])}, x, off,
            JOB, NM)
        plain = REF.forward(flat, x, off, JOB, NM)
    np.testing.assert_allclose(np.asarray(scaled), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
    assert float(jnp.abs(scaled - plain).max()) > 1e-2


def test_the_layers_read_grouped_heads_lane_indexed_under_the_window():
    """At the published head width the flash entry takes 32 query heads over
    4 key/value heads where the projections hold them, on both kinds of
    layer: lane-indexed, no ``repeat`` of the key/value heads; the sliding
    layers' calls run under the window and execute its tiles alone."""
    cfg = dict(CFG, num_attention_heads=32, num_key_value_heads=4,
               num_hidden_layers=8, sliding_window=2048)
    fn = functools.partial(flash_attention, causal=True, block_q=512,
                           block_k=512)
    gauges = _module(cfg, attention_fn=fn, remat=True).build_gauges(
        (1, 8192), 4)
    # runs [SS] [S] [F] [SSS] [F]: a run traces its call once
    assert (gauges["flash_calls_lane_indexed"],
            gauges["flash_calls_transposed"]) == (5, 0)
    assert (gauges["flash_calls_window"], gauges["flash_calls_full"]) == (3, 2)
    assert gauges["flash_window"] == 2048
    assert (gauges["flash_window_tiles_live"],
            gauges["flash_window_tiles_causal"]) == (70, 136)
    # 32,768 tokens in flight: the routed layer's chunks are 2,048 rows
    assert gauges["moe_chunk_rows"] == 2048
    # how a head of the newest causal call, the last full layer's, walks
    # its live range: the diagonal's 16 tiles build the positional mask, 120
    # do not, and three block steps a query block sit behind a condition
    assert (gauges["flash_tiles_edge"], gauges["flash_tiles_interior"],
            gauges["flash_cond_steps"]) == (16, 120, 48)
    assert gauges["remat_saved_names"] == 2
    # the stream is not kept: out [4, 8192, 4096] bf16... here float32
    assert gauges["remat_saved_bytes_per_layer"] == 4 * (
        8192 * 32 * 128 * 4 + 32 * 8192 * 4)


def test_build_gauges_state_the_static_facts():
    gauges = _module(attention_fn=FLASH, remat=True).build_gauges((1, 20), 4)
    assert {k: gauges[k] for k in (
        "moe_experts_held", "moe_router_width", "moe_top_k", "flash_window")
    } == {"moe_experts_held": 8, "moe_router_width": 40, "moe_top_k": 6,
          "flash_window": 6}
    # how the held rows travel (``routed.routed_gauges``): at 80 tokens a
    # chunk is one tile and each of the 8 held experts has a chunk of its own
    assert {k: gauges[k] for k in (
        "moe_tile_rows", "moe_chunk_rows", "moe_row_moves_per_pass")} == {
            "moe_tile_rows": 256, "moe_chunk_rows": 256,
            "moe_row_moves_per_pass": 16}
    # runs [SS] [S] [F] [SS]: three sliding runs and a full one
    assert (gauges["flash_calls_window"], gauges["flash_calls_full"]) == (3, 1)
    # T 20 padded to 24 under blocks of 8, a window of 6: the diagonal tile
    # and the one before it
    assert (gauges["flash_window_tiles_live"],
            gauges["flash_window_tiles_causal"]) == (5, 6)
    assert gauges["remat_saved_names"] == 2
    dense = _module().build_gauges((1, 20), 4)
    assert dense["remat_saved_names"] == 0
    assert (dense["flash_calls_window"], dense["flash_calls_full"]) == (0, 0)


def test_the_module_brings_its_own_split_and_cast(seeded):
    _, tree, x = seeded
    module = _module(dtype=jnp.bfloat16)
    model = engine.from_flax(module)
    per_client, shared = ptu.split_by_path(tree, model.per_client)
    assert {k.rsplit("/", 1)[-1] for k in build.flatten(per_client)} == {
        "lora_a", "lora_b", "kernel"}
    assert [k for k in build.flatten(per_client) if k.endswith("kernel")] == [
        "score/kernel"]
    prepared = build.flatten(module.prepare_shared(shared))
    dtypes = {k: str(v.dtype) for k, v in prepared.items()}
    # matmul operands in the compute type; the router, its bias, the norms
    # and the embedding stay float32
    assert dtypes["runs/0/self_attn/gate_proj/kernel"] == "bfloat16"
    assert dtypes["runs/1/mlp/experts_3/up_proj/kernel"] == "bfloat16"
    assert dtypes["runs/0/mlp/down_proj/kernel"] == "bfloat16"
    for name in ("runs/1/mlp/router/kernel", "runs/1/mlp/expert_bias",
                 "runs/0/self_attn/q_norm/scale",
                 "runs/0/pre_mlp_layernorm/scale", "embed_tokens/embedding"):
        assert dtypes[name] == "float32", name
    # the two dense sliding layers are one stack of two
    assert prepared["runs/0/self_attn/q_proj/kernel"].shape[0] == 2
    out = module.bind_shared(shared)(per_client, x)[0]["prediction"]
    assert out.shape == (3, 4) and out.dtype == jnp.float32
    # bfloat16 compute on float32 masters stays near the float32 forward
    want = _module().apply({"params": tree}, x)[0]["prediction"]
    assert float(jnp.max(jnp.abs(out - want))) < 0.4
    assert model.bind_shared is not None and model.build_gauges is not None


def test_experts_outside_the_router_and_unknown_layers_are_refused():
    with pytest.raises(ValueError, match="not among the router's 40"):
        _module(dict(CFG, first_expert_held=36)).dims
    with pytest.raises(ValueError, match="a layer is"):
        _module(dict(CFG, layer_types=[S, "chunked_attention"],
                     num_hidden_layers=2)).dims


# -- the shares add up ---------------------------------------------------------
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Five shares of eight experts each, all routing over the same 40: each
    share's routed part, plus the shared expert counted once, is the
    reference's UNCUT layer (40 held). No share stands in for an absent
    one."""
    uncut = dict(CFG, num_experts=40, first_expert_held=0,
                 num_hidden_layers=3)
    flat = _weights(uncut, 5)
    p = _layer_leaves(flat, 2)
    s = REF.sizes(uncut, JOB)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 32))
    ones = jnp.ones(u.shape[:2])
    with jax.default_matmul_precision("highest"):
        want = REF._moe(p, u, ones, s, NM)
        mlp = build.nest(p)["mlp"]
        routed = jnp.zeros_like(want)
        for first in range(0, 40, 8):
            cfg = dict(uncut, num_experts=8, first_expert_held=first)
            share = dict(mlp, **{f"experts_{j}": mlp[f"experts_{first + j}"]
                                 for j in range(8)})
            dims = _module(cfg).dims
            both = afmoe.moe(share, u, ones, dims)
            shared_only = afmoe.swiglu(share["shared_experts"], u, dims)
            routed = routed + (both - shared_only)
        got = routed + afmoe.swiglu(mlp["shared_experts"], u, dims)
    # float32 summation order over 40 experts in five partial sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=2e-4)
    # and one share alone is not the layer
    assert float(jnp.abs(both - want).max()) > 1e-2


def test_a_pad_position_picks_no_expert_and_the_tokens_are_untouched():
    """Pad positions (the tail) go through the shared expert alone, in the
    program and in the reference alike; a token's output is what it is
    without the mask."""
    flat = _weights(CFG, 7)
    leaves = _layer_leaves(flat, 2)
    p = build.nest(leaves)["mlp"]
    dims = _module().dims
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 32))
    mask = (jnp.arange(12)[None, :] < jnp.asarray([[12], [7]])).astype(
        jnp.float32)
    with jax.default_matmul_precision("highest"):
        masked = afmoe.moe(p, u, mask, dims)
        plain = afmoe.moe(p, u, jnp.ones((2, 12)), dims)
        shared = afmoe.swiglu(p["shared_experts"], u, dims)
        ref = REF._moe(leaves, u, mask, REF.sizes(CFG, JOB), NM)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(ref),
                               atol=3e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(masked[0]), np.asarray(plain[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(masked[1, :7]),
                               np.asarray(plain[1, :7]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(masked[1, 7:]),
                               np.asarray(shared[1, 7:]), atol=1e-6)
    # and the routed part was something there before the mask
    assert float(jnp.abs(plain[1, 7:] - shared[1, 7:]).max()) > 1e-3
