"""``models/deepseek.py`` against the benchmark's plain float32 reference
(``benchmarks/reference/deepseek_v2_classifier.py``: jax.numpy, dense causal
attention, every held expert over every token under a mask, nothing of the
program) at toy widths on seeded weights: YaRN's frequencies against hand
counts, the forward and every adapter gradient, the share test that ties
twenty shares of eight experts to the uncut layer, the dropless row bound,
and the folded client axis against ``vmap``."""

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
from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.models import deepseek as ds
from fl4health_tpu.models import routed
from fl4health_tpu.models.decoder_common import rms_norm, swiglu
from tests.models.remat_probe import eqns, pallas_calls, products_with

REF = load_module("reference", "deepseek_v2_classifier")
NM = load_module("reference", "numerics").FLOAT32
# the published routing (8 groups, the best 3, 6 a token) over 40 experts of
# which 8 are held, from the sixth: they span two groups
CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "n_routed_experts": 8, "first_expert_held": 5, "router_width": 40,
    "n_shared_experts": 2, "n_group": 8, "topk_group": 3,
    "num_experts_per_tok": 6, "routed_scaling_factor": 16,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "vocab_size": 50, "num_labels": 4, "lora_rank": 2, "lora_alpha": 4,
    "rms_norm_eps": 1e-6,
}
JOB = {"data": {"seq": 20, "min_len_frac": 0.5}}
PUBLISHED_ROPE = ds.RopeScaling(theta=10000.0, factor=40.0, beta_fast=32.0,
                                beta_slow=1.0, original_max_position=4096,
                                mscale=0.707, mscale_all_dim=0.707)


def _module(cfg=CFG, attention_fn=None, remat=False, dtype=jnp.float32):
    c, r = cfg, cfg["rope_scaling"]
    return ds.DeepseekV2Classifier(
        vocab_size=c["vocab_size"], n_classes=c["num_labels"],
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        first_k_dense=c["first_k_dense_replace"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_expert=c["moe_intermediate_size"],
        n_routed_experts=c["router_width"],
        experts_held=c["n_routed_experts"],
        first_expert_held=c["first_expert_held"],
        n_shared_experts=c["n_shared_experts"], n_group=c["n_group"],
        topk_group=c["topk_group"], top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        rope=ds.RopeScaling(
            theta=float(c["rope_theta"]), factor=float(r["factor"]),
            beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
            original_max_position=r["original_max_position_embeddings"],
            mscale=r["mscale"], mscale_all_dim=r["mscale_all_dim"]),
        rms_eps=c["rms_norm_eps"], lora_rank=c["lora_rank"],
        lora_alpha=float(c["lora_alpha"]), dtype=dtype, remat=remat,
        attention_fn=attention_fn)


def _weights(cfg, seed):
    with jax.default_matmul_precision("highest"):
        return datagen.make_weights(REF.param_spec(cfg, JOB), seed)


@pytest.fixture(scope="module")
def seeded():
    """(flat reference weights, the same as the program's tree, tokens)."""
    flat = _weights(CFG, 11)
    x = np.random.default_rng(0).integers(1, CFG["vocab_size"], (3, 20))
    x[1, 13:] = 0  # a padded tail
    return flat, build.nest(flat), jnp.asarray(x, jnp.int32)


# -- rotary positions --------------------------------------------------------
def test_yarn_frequencies_and_mscale_by_hand():
    """find_correction_range(32, 1, 64, 10000, 4096) = (10, 23): dimensions
    below 10 keep theta^(-2i/64), above 23 have it divided by 40, a linear
    ramp between; mscale = 0.1 * 0.707 * ln 40 + 1."""
    inv = ds.yarn_inv_freq(64, PUBLISHED_ROPE)
    assert len(inv) == 32
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    for i in range(11):
        assert inv[i] == pytest.approx(plain[i], rel=1e-12)
    for i in range(23, 32):
        assert inv[i] == pytest.approx(plain[i] / 40, rel=1e-12)
    # dimension 16: theta^-0.5 = 0.01, six thirteenths of the way down
    assert inv[16] == pytest.approx(0.01 * (7 / 13) + 0.01 / 40 * (6 / 13),
                                    rel=1e-12)
    assert ds.yarn_get_mscale(40, 0.707) == pytest.approx(1.260804, rel=1e-6)
    assert ds.softmax_scale(192, PUBLISHED_ROPE) == pytest.approx(
        192 ** -0.5 * 1.260804 ** 2, rel=1e-6)
    assert ds.softmax_scale(192, PUBLISHED_ROPE) == pytest.approx(0.114721,
                                                                  rel=1e-5)
    # the reference computes its own, from the configuration's keys
    cfg = dict(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=dict(CFG["rope_scaling"],
                                 original_max_position_embeddings=4096))
    s = REF.sizes(cfg, JOB)
    np.testing.assert_allclose(s["inv_freq"], inv, rtol=1e-12)
    assert s["softmax_scale"] == pytest.approx(
        ds.softmax_scale(192, PUBLISHED_ROPE), rel=1e-12)
    # cos and sin carry mscale / mscale_all_dim = 1; without YaRN plain RoPE
    cos, sin = ds.rope_tables(5, 64, PUBLISHED_ROPE)
    np.testing.assert_allclose(cos[3], np.cos(3 * np.asarray(inv)), rtol=1e-5)
    np.testing.assert_allclose(sin[3], np.sin(3 * np.asarray(inv)), rtol=1e-5,
                               atol=1e-7)
    assert ds.yarn_inv_freq(8, ds.RopeScaling()) == [
        10000.0 ** (-2 * i / 8) for i in range(4)]
    assert ds.softmax_scale(64, ds.RopeScaling()) == 0.125


def test_rope_rotates_pairs_in_the_halves_layout():
    cos, sin = ds.rope_tables(4, 4, ds.RopeScaling())
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4).repeat(4, 1)
    got = np.asarray(ds.apply_rope(x, cos, sin))[0, :, 0]
    for t in range(4):
        for i, f in enumerate((1.0, 0.01)):
            c, s = math.cos(t * f), math.sin(t * f)
            x1, x2 = (1.0, 3.0) if i == 0 else (2.0, 4.0)
            assert got[t, i] == pytest.approx(x1 * c - x2 * s, abs=1e-5)
            assert got[t, 2 + i] == pytest.approx(x2 * c + x1 * s, abs=1e-5)


# -- the whole model ---------------------------------------------------------
def test_the_programs_tree_is_the_references_param_spec(seeded):
    flat, tree, x = seeded
    init = _module().init(jax.random.PRNGKey(0), x, train=False)["params"]
    got = {k: tuple(v.shape) for k, v in build.flatten(init).items()}
    assert got == {k: tuple(v.shape) for k, v in flat.items()}
    # every expert's matrices are leaves of their own
    assert "layers_1/mlp/experts_7/down_proj/kernel" in got
    assert "layers_0/mlp/gate_proj/lora_a" in got
    assert not any("experts_" in k and "lora" in k for k in got)
    assert "layers_1/mlp/gate/lora_a" not in got


def test_router_picks_what_the_reference_picks(seeded):
    flat, tree, _ = seeded
    s = REF.sizes(CFG, JOB)
    u = jax.random.normal(jax.random.PRNGKey(3), (64, s["d"]))
    idx, w = ds.route(tree["layers_1"]["mlp"]["gate"], u, _module().dims)
    want = np.asarray(REF.route(flat["layers_1/mlp/gate/kernel"], u, s))
    assert idx.shape == (64, 6) and (np.count_nonzero(want, axis=1) == 6).all()
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # at most three of the eight groups of five
    assert (np.array([len({i // 5 for i in row}) for row in np.asarray(idx)])
            <= 3).all()


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_forward_and_adapter_gradients_match_the_reference(seeded, attention,
                                                           remat):
    flat, tree, x = seeded
    fn = (functools.partial(flash_attention, causal=True, block_q=8,
                            block_k=8) if attention == "flash" else None)
    module = _module(attention_fn=fn, remat=remat)
    y = jnp.asarray([0, 3, 1])

    def ce(logits):
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(3), y])

    logits = module.apply({"params": tree}, x)[0]["prediction"]
    want = REF.forward(flat, x, CFG, JOB, NM)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
    assert abs(float(ce(logits)) - float(ce(want))) < 1e-5
    per_client, shared = ptu.split_by_path(tree, module.per_client_param)
    # the engine's path: the shared half prepared (cast, stacked) once, the
    # client's half beside it
    forward = module.bind_shared(shared)
    np.testing.assert_allclose(
        np.asarray(forward(per_client, x)[0]["prediction"]),
        np.asarray(logits), atol=1e-6)
    got = build.flatten(jax.grad(
        lambda p: ce(forward(p, x)[0]["prediction"]))(per_client))
    names = set(got)
    ref_grad = jax.grad(lambda p: ce(REF.forward({**flat, **p}, x, CFG, JOB,
                                                 NM)))(
        {k: flat[k] for k in names})
    # five projections of the attention in each of three layers, three of
    # the dense MLP, three of the shared experts in two layers, and the head
    assert len(names) == 2 * (3 * 5 + 3 + 2 * 3) + 1
    for k in sorted(names):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref_grad[k]),
                                   atol=3e-5, rtol=2e-4, err_msg=k)
    # the router's input trains what lies before it: the second layer's
    # combine weights carry gradient into the first layer's adapters
    assert float(jnp.abs(got["layers_0/mlp/down_proj/lora_b"]).max()) > 0


@pytest.mark.parametrize("site", ["kept", "bare"])
def test_a_rematerialised_layer_recomputes_neither_flash_nor_o_proj(
        seeded, site, monkeypatch):
    """Two runs of layers, each one traced layer: with the kernel's results
    and the attention's output stream kept (the site's policy) the gradient
    holds ONE ``flash_fwd`` and ONE product with ``o_proj``'s kernel a
    traced layer, the forward's; under a bare ``jax.checkpoint`` (what the
    site was) two of each. A value head of 24 makes the kernel's shape,
    [4 * 24, 32], no other matrix's. The gradients are those without remat
    (at the seeded widths, the reference's: the test above)."""
    if site == "bare":
        monkeypatch.setattr(remat_names, "keep", lambda names: None)
    cfg = dict(CFG, v_head_dim=24)
    tree, x = build.nest(_weights(cfg, 11)), seeded[2]
    fn = functools.partial(flash_attention, causal=True, block_q=8, block_k=8)

    def grad(remat):
        module = _module(cfg, attention_fn=fn, remat=remat)
        per_client, shared = ptu.split_by_path(tree, module.per_client_param)
        forward = module.bind_shared(shared)
        return jax.grad(lambda p: jnp.sum(jnp.square(
            forward(p, x)[0]["prediction"]))), per_client

    fn_remat, per_client = grad(True)
    jaxpr = jax.make_jaxpr(fn_remat)(per_client).jaxpr
    twice = 1 if site == "kept" else 2
    assert {name: pallas_calls(jaxpr, name)
            for name in ("flash_fwd", "flash_dq", "flash_dkv")} == {
        "flash_fwd": 2 * twice, "flash_dq": 2, "flash_dkv": 2}
    assert products_with(jaxpr, (4 * 24, 32)) == 2 * twice
    # the other four projections ARE recomputed: kv_b_proj's no-position
    # columns [8, 4 * 8] in the forward and in the recompute of each run
    assert products_with(jaxpr, (8, 4 * 8)) == 4
    got, want = fn_remat(per_client), grad(False)[0](per_client)
    for k, v in build.flatten(want).items():
        np.testing.assert_allclose(np.asarray(build.flatten(got)[k]),
                                   np.asarray(v), atol=3e-5, rtol=2e-4,
                                   err_msg=k)


@pytest.mark.parametrize("attention,names,per_client", [
    # out [B * 4, Tp, 64] (a value head of 8 padded to 64 lanes), the
    # statistic [B * 4, Tp], 20 positions padded to the blocks of 8, and the
    # stream [B, T, 32]
    ("flash", 3, 8 * 24 * 64 * 4 + 8 * 24 * 4 + 2 * 20 * 32 * 4),
    # the dense form names nothing of its own: the stream alone
    ("dense", 1, 2 * 20 * 32 * 4),
])
def test_build_gauges_say_what_a_rematerialised_layer_keeps(attention, names,
                                                            per_client):
    fn = (functools.partial(flash_attention, causal=True, block_q=8,
                            block_k=8) if attention == "flash" else None)
    gauges = _module(attention_fn=fn, remat=True).build_gauges((2, 20), 4)
    assert gauges["remat_saved_names"] == names
    assert gauges["remat_saved_bytes_per_layer"] == 4 * per_client
    assert gauges["flash_calls_transposed"] == 2 * (attention == "flash")


def test_latent_attention_hands_the_flash_calls_its_parts_where_they_lie():
    """At the published head widths (128 without positions + 64 rotary, 128
    values) the flash entry is lane-indexed: between the projections and the
    calls nothing is padded, concatenated to 192 lanes or broadcast over
    the heads, and the one transpose is of the rotary part of q (64 lanes a
    head are no lane block: it lies [B, heads, T, 64])."""
    heads, t = 6, 256
    cfg = dict(CFG, num_attention_heads=heads, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128)
    module = _module(cfg, attention_fn=functools.partial(
        flash_attention, causal=True, block_q=128, block_k=128))
    gauges = module.build_gauges((1, t), 4)
    # the dense run and the expert run trace their call once each
    assert gauges["flash_calls_lane_indexed"] == 2
    assert gauges["flash_calls_transposed"] == 0
    assert _module(cfg).build_gauges((1, t), 4)["flash_calls_lane_indexed"] == 0

    x = jax.ShapeDtypeStruct((1, t), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
    u = jax.ShapeDtypeStruct((1, t, cfg["hidden_size"]), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, t), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, u, m: ds.mla_attention(p, u, m, module.dims))(
        params["layers_0"]["self_attn"], u, mask).jaxpr
    moved = [(e.primitive.name, tuple(e.invars[0].aval.shape),
              tuple(e.outvars[0].aval.shape))
             for e in eqns(jaxpr)
             if e.primitive.name in ("transpose", "pad", "broadcast_in_dim",
                                     "concatenate")
             # an array with the heads in it (as an axis, or in the lanes)
             and any(d in (heads, heads * 128, heads * 64)
                     for d in e.outvars[0].aval.shape)]
    rope_halves = ("concatenate", (1, t, heads, 32), (1, t, heads, 64))
    assert [m for m in moved if m != rope_halves] == [
        ("transpose", (1, t, heads, 64), (1, heads, t, 64))], moved
    assert sum(e.primitive.name == "pallas_call"
               for e in eqns(jaxpr)) == 1


def test_the_module_brings_its_own_split_and_cast(seeded):
    _, tree, x = seeded
    module = _module(dtype=jnp.bfloat16)
    model = engine.from_flax(module)
    per_client, shared = ptu.split_by_path(tree, model.per_client)
    assert {k.rsplit("/", 1)[-1] for k in build.flatten(per_client)} == {
        "lora_a", "lora_b", "kernel"}
    assert [k for k in build.flatten(per_client) if k.endswith("kernel")] == [
        "score/kernel"]
    captured = {"tree": module.prepare_shared(shared)}
    got = module.bind_shared(shared)(per_client, x)[0]["prediction"]
    for k, v in build.flatten(captured["tree"]).items():
        matrix = k.endswith("/kernel") and "/gate/" not in k
        assert v.dtype == (jnp.bfloat16 if matrix else jnp.float32), k
    # the four expert layers' leaves are one stack each: [layers, ...]
    assert captured["tree"]["runs"]["1"]["mlp"]["experts_0"]["up_proj"][
        "kernel"].shape == (2, 32, 16)
    assert captured["tree"]["runs"]["1"]["mlp"]["gate"]["kernel"].dtype == jnp.float32
    want = _module().apply({"params": tree}, x)[0]["prediction"]
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.25
    assert module.build_gauges((1, 20), 4) == {
        "moe_experts_held": 8, "moe_experts_total": 40,
        "moe_assignment_rows_bound": 4 * 20 * 6,
        # 80 tokens: a chunk is one tile, and each of the 8 held experts
        # has a chunk of its own: a gather and a combine a chunk
        "moe_tile_rows": 256, "moe_chunk_rows": 256,
        "moe_row_moves_per_pass": 16,
        "flash_calls_lane_indexed": 0, "flash_calls_transposed": 0,
        "remat_saved_names": 0, "remat_saved_bytes_per_layer": 0}


def test_experts_outside_the_router_are_refused():
    with pytest.raises(ValueError, match="not among the router's 40"):
        _module(dict(CFG, first_expert_held=33)).dims  # noqa: B018


# -- the share of a layer ----------------------------------------------------
def test_twenty_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the cut: over all 20 shares of 8
    experts, the routed parts, with the attention and the shared experts
    (which every chip computes alike) counted once, equal what the uncut
    reference gives for the whole 160-expert layer."""
    uncut = dict(CFG, router_width=160, n_routed_experts=160,
                 first_expert_held=0, num_hidden_layers=2)
    flat = _weights(uncut, 23)
    tree = build.nest(flat)["layers_1"]
    s = REF.sizes(uncut, JOB)
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 24, s["d"]))
    pad_mask = jnp.ones((2, 24)).at[1, 17:].set(0.0)
    p_ref = {k[len("layers_1/"):]: v for k, v in flat.items()
             if k.startswith("layers_1/")}
    want = REF._layer(p_ref, h, pad_mask, True, s, NM)
    total = None
    for share in range(20):
        module = _module(dict(uncut, n_routed_experts=8,
                              first_expert_held=8 * share))
        dims = module.dims
        if total is None:  # what every chip computes alike, once
            u = rms_norm(h, tree["input_layernorm"]["scale"], dims.rms_eps)
            total = h + ds.mla_attention(tree["self_attn"], u, pad_mask, dims)
            u = rms_norm(total, tree["post_attention_layernorm"]["scale"],
                         dims.rms_eps)
            total = total + swiglu(tree["mlp"]["shared_experts"], u, dims)
            flat_u = u.reshape(-1, s["d"])
            idx, w = ds.route(tree["mlp"]["gate"], flat_u, dims)
            assert int(idx.max()) > 150 and int(idx.min()) < 8
        experts = [tuple(tree["mlp"][f"experts_{8 * share + j}"][name]["kernel"]
                         for name in ("gate_proj", "up_proj", "down_proj"))
                   for j in range(8)]
        total = total + routed.routed_experts(
            flat_u, idx, w, experts, dims.first_expert_held).reshape(h.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
    # and one share alone is what the reference gives when given that share
    cut = dict(uncut, n_routed_experts=8, first_expert_held=40)
    s_cut = REF.sizes(cut, JOB)
    p_cut = {k: v for k, v in p_ref.items() if "/experts_" not in k}
    p_cut.update({f"mlp/experts_{j}/{n}/kernel":
                  p_ref[f"mlp/experts_{40 + j}/{n}/kernel"]
                  for j in range(8)
                  for n in ("gate_proj", "up_proj", "down_proj")})
    tree_cut = build.nest(p_cut)
    got = ds.layer(tree_cut, h, pad_mask, True, _module(cut).dims)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(REF._layer(p_cut, h, pad_mask, True,
                                               s_cut, NM)),
        atol=3e-5, rtol=3e-5)


# -- the routed part alone ---------------------------------------------------
def _experts(key, held, d=16, f=12):
    ks = jax.random.split(key, 3 * held)
    return [(jax.random.normal(ks[3 * j], (d, f)) / 4,
             jax.random.normal(ks[3 * j + 1], (d, f)) / 4,
             jax.random.normal(ks[3 * j + 2], (f, d)) / 3)
            for j in range(held)]


def _dense_routed(x, idx, w, experts, first):
    """Every held expert over every row under a mask."""
    y = jnp.zeros(x.shape, jnp.float32)
    for j, (gate, up, down) in enumerate(experts):
        combine = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=1)
        y = y + combine[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)
    return y


def test_dropless_when_every_choice_lands_on_a_held_expert():
    """A router so skewed that all six choices of every token are held here:
    the row bound (tokens x 6) is reached, experts need several tiles, one
    expert gets no row at all, and nothing is dropped."""
    n, k, held, first = 300, 6, 8, 16
    experts = _experts(jax.random.PRNGKey(0), held)
    rng = np.random.default_rng(1)
    # six distinct held experts a token, never the last one; the first is in
    # every token's six, so it needs two tiles of 256 rows
    idx = np.stack([np.concatenate([[0], 1 + rng.permutation(held - 2)[:k - 1]])
                    for _ in range(n)]) + first
    idx = jnp.asarray(idx, jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (n, k), minval=0.2)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 16))
    order, tok, _, starts, counts = routed._plan(idx, w, first, held)
    assert int(counts.sum()) == n * k
    assert int(counts[0]) == n > routed.TILE_ROWS
    assert int(counts[-1]) == 0 and tok.shape == (n * k + routed.TILE_ROWS,)
    np.testing.assert_array_equal(np.asarray(starts),
                                  np.cumsum(counts) - np.asarray(counts))
    got = routed.routed_experts(x, idx, w, experts, first)
    want = _dense_routed(x, idx, w, experts, first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # every token got all six of its experts: none of them reads zero
    assert float(jnp.abs(got).sum(axis=1).min()) > 0
    gx, gw = jax.grad(lambda x, w: jnp.sum(jnp.sin(routed.routed_experts(
        x, idx, w, experts, first))), argnums=(0, 1))(x, w)
    wx, ww = jax.grad(lambda x, w: jnp.sum(jnp.sin(_dense_routed(
        x, idx, w, experts, first))), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=2e-5,
                               rtol=2e-4)


def test_choices_held_elsewhere_add_nothing_and_get_no_gradient():
    n, k, held, first = 40, 3, 4, 8
    experts = _experts(jax.random.PRNGKey(5), held)
    idx = jnp.asarray(np.random.default_rng(2).integers(0, 32, (n, k)),
                      jnp.int32)
    w = jnp.ones((n, k))
    x = jax.random.normal(jax.random.PRNGKey(6), (n, 16))
    local = (idx >= first) & (idx < first + held)
    assert 0 < int(local.sum()) < n * k
    got = routed.routed_experts(x, idx, w, experts, first)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense_routed(x, idx, w, experts, first)),
        atol=2e-5, rtol=2e-5)
    none_local = ~local.any(axis=1)
    assert none_local.any() and float(jnp.abs(got[none_local]).max()) == 0.0
    gw = jax.grad(lambda w: jnp.sum(routed.routed_experts(x, idx, w, experts,
                                                      first)))(w)
    assert float(jnp.abs(jnp.where(local, 0.0, gw)).max()) == 0.0
    assert float(jnp.abs(jnp.where(local, gw, 1.0)).min()) > 0.0


def test_the_folded_client_axis_is_vmap_of_the_per_client_form():
    """Under ``vmap`` over clients the routed part runs ONCE over all the
    clients' rows (the experts carry no client axis); values and gradients
    are those of one call a client."""
    c, n, k, held, first = 3, 50, 2, 4, 4
    experts = _experts(jax.random.PRNGKey(7), held)
    idx = jnp.asarray(np.random.default_rng(3).integers(0, 12, (c, n, k)),
                      jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(8), (c, n, k), minval=0.1)
    x = jax.random.normal(jax.random.PRNGKey(9), (c, n, 16))

    def one(x, idx, w):
        return routed.routed_experts(x, idx, w, experts, first)

    def value_and_grads(x, idx, w):
        return one(x, idx, w), jax.grad(
            lambda x, w: jnp.sum(jnp.cos(one(x, idx, w))), argnums=(0, 1))(x, w)

    folded = jax.jit(jax.vmap(value_and_grads))(x, idx, w)
    each = [value_and_grads(x[i], idx[i], w[i]) for i in range(c)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *each)
    for got, want in zip(jax.tree_util.tree_leaves(folded),
                         jax.tree_util.tree_leaves(stacked)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
    # one sort of all the clients' pairs, not one a client
    text = str(jax.make_jaxpr(jax.vmap(one))(x, idx, w))
    assert text.count(" sort[") == 1 and f"{c * n * k}]" in text
    # experts that do carry the axis get the plain vmap
    per_client_experts = [tuple(jnp.stack([m, 2 * m, 3 * m]) for m in e)
                          for e in experts]
    got = jax.vmap(lambda x, idx, w, e: routed.routed_experts(
        x, idx, w, e, first))(x, idx, w, per_client_experts)
    want = jnp.stack([routed.routed_experts(
        x[i], idx[i], w[i], [tuple((i + 1) * m for m in e) for e in experts],
        first) for i in range(c)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
