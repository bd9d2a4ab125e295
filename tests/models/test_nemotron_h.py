"""``models/nemotron_h.py`` against the benchmark's plain float32 reference
(``benchmarks/reference/nemotron_h_classifier.py``: jax.numpy, the Mamba-2
recurrence one position after another, dense causal attention over repeated
key/value heads, every held expert over every token under a mask, nothing of
the program) at toy widths on seeded weights: the tree, the router's picks
under a non-zero selection bias, the chunked scan against the step
recurrence, the forward and every adapter gradient, the shares of a layer
that add up to the uncut layer, the pattern's runs, the split and the
gauges. Every tolerance says what it is for and what it refuses."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import build, datagen
from benchmarks.harness.spec import load_module
from fl4health_tpu.clients import engine
from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.kernels.ssd_scan import ssd_scan, ssd_scan_xla
from fl4health_tpu.models import nemotron_h as nh
from fl4health_tpu.models.decoder_common import pattern_runs
from tests.models.remat_probe import eqns

REF = load_module("reference", "nemotron_h_classifier")
NM = load_module("reference", "numerics").FLOAT32
# the published structure at toy widths: one Mamba-2, expert and attention
# block each and a unit that repeats; 4 query heads of 128 lanes over 2
# key/value heads (the flash calls' grouped addressing), 8 of 40 experts held
# from the sixth, 6 a token, renormalised and scaled by 5, a chunk of 8
CFG = {
    "hidden_size": 32, "num_hidden_layers": 6,
    "hybrid_override_pattern": "MEME*EMM",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 8, "first_expert_held": 5, "router_width": 40,
    "num_experts_per_tok": 6, "routed_scaling_factor": 5,
    "moe_latent_size": 16, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48,
    "vocab_size": 50, "num_labels": 4, "lora_rank": 2, "lora_alpha": 4,
    "layer_norm_epsilon": 1e-5,
}
JOB = {"data": {"seq": 20, "min_len_frac": 0.5}}
FLASH = functools.partial(flash_attention, causal=True, block_q=8, block_k=8)


def _module(cfg=CFG, attention_fn=None, remat=False, dtype=jnp.float32):
    c = cfg
    return nh.NemotronHClassifier(
        vocab_size=c["vocab_size"], n_classes=c["num_labels"],
        pattern=c["hybrid_override_pattern"][:c["num_hidden_layers"]],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_groups=c["n_groups"], ssm_state=c["ssm_state_size"],
        d_conv=c["conv_kernel"], chunk=c["chunk_size"],
        n_routed_experts=c["router_width"],
        experts_held=c["n_routed_experts"],
        first_expert_held=c["first_expert_held"],
        top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        d_latent=c["moe_latent_size"], d_expert=c["moe_intermediate_size"],
        d_shared=c["moe_shared_expert_intermediate_size"],
        rms_eps=c["layer_norm_epsilon"], lora_rank=c["lora_rank"],
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


# -- structure ---------------------------------------------------------------
def test_the_programs_tree_is_the_references_param_spec(seeded):
    flat, _, x = seeded
    init = _module().init(jax.random.PRNGKey(0), x, train=False)["params"]
    got = {k: tuple(v.shape) for k, v in build.flatten(init).items()}
    assert got == {k: tuple(v.shape) for k, v in flat.items()}
    # a block is one mixer behind one norm; every expert's two matrices are
    # leaves of their own; experts and router carry no adapter
    assert {k.split("/")[1] for k in got if k.startswith("layers_")} == {
        "norm", "mixer"}
    assert got["layers_1/mixer/experts_7/down_proj/kernel"] == (24, 16)
    assert got["layers_1/mixer/gate/e_score_correction_bias"] == (40,)
    assert got["layers_0/mixer/in_proj/kernel"] == (32, 2 * 32 + 2 * 32 + 4)
    assert got["layers_0/mixer/A_log"] == (4,)  # ONE decay a head
    assert not any(("experts_" in k or "/gate/" in k) and "lora" in k
                   for k in got)
    # nor do the latent projections, which only the routed experts read
    assert not any("latent_proj" in k and "lora" in k for k in got)
    assert "layers_1/mixer/shared_experts/up_proj/lora_b" in got


@pytest.mark.parametrize("pattern,want", [
    ("MEMEMEM*EME", [[(0, 1), (2, 3), (4, 5)], [(6,)], [(7,)], [(8,)],
                     [(9,)], [(10,)]]),
    ("MEME*E", [[(0, 1), (2, 3)], [(4,)], [(5,)]]),
    ("MMM*", [[(0,), (1,), (2,)], [(3,)]]),
    ("EMEMEMEM*EMEM", [[(0, 1), (2, 3), (4, 5), (6, 7)], [(8,)],
                       [(9, 10), (11, 12)]]),
    ("M", [[(0,)]]),
])
def test_the_pattern_is_cut_into_units_that_repeat(pattern, want):
    """A run is one ``lax.scan``: like blocks, or a unit of two unlike ones
    and its repeats (the published pattern alternates); every block is in
    exactly one run, in order."""
    runs = pattern_runs(pattern, nh.MAX_UNIT)
    assert runs == want
    assert [i for run in runs for unit in run for i in unit] == list(
        range(len(pattern)))


# -- the router ----------------------------------------------------------------
def test_router_picks_what_the_reference_picks(seeded):
    """With the seeded, NON-ZERO selection bias: the picks follow ``s + b``,
    the weights ``s`` alone, renormalised over the chosen and scaled by 5."""
    flat, tree, _ = seeded
    s = REF.sizes(CFG, JOB)
    gate = tree["layers_1"]["mixer"]["gate"]
    assert float(jnp.abs(gate["e_score_correction_bias"]).max()) > 0.01
    u = jax.random.normal(jax.random.PRNGKey(3), (64, s["d"]))
    idx, w = nh.sigmoid_route(gate, u, 6, 5.0)
    p = {k[len("layers_1/"):]: v for k, v in flat.items()
         if k.startswith("layers_1/")}
    want = np.asarray(REF.route(p, u, s))
    assert idx.shape == (64, 6) and (np.count_nonzero(want, axis=1) == 6).all()
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    # float32 on both sides: 1e-5 is rounding; a bfloat16 router reads 4e-3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 5.0, rtol=1e-5)
    # the bias picks: without it some tokens would choose otherwise
    plain, _ = nh.sigmoid_route(
        dict(gate, e_score_correction_bias=jnp.zeros(40)), u, 6, 5.0)
    assert (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(idx), 1)).any()
    # ... and does not weigh: a weight is 5 s / sum s of the unbiased scores
    scores = jax.nn.sigmoid(jnp.dot(u, gate["kernel"],
                                    precision=jax.lax.Precision.HIGHEST))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w), 5 * chosen / chosen.sum(
        1, keepdims=True), rtol=1e-5)


def test_a_bfloat16_router_is_outside_the_routers_tolerance(seeded):
    """What rtol 1e-5 above refuses: logits from bfloat16 operands move the
    weights by some 1e-3 and flip near-tied picks."""
    _, tree, _ = seeded
    gate = tree["layers_1"]["mixer"]["gate"]
    u = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    _, w = nh.sigmoid_route(gate, u, 6, 5.0)
    low = dict(gate, kernel=gate["kernel"].astype(jnp.bfloat16))
    _, w_low = nh.sigmoid_route(low, u.astype(jnp.bfloat16), 6, 5.0)
    assert float(jnp.abs(jnp.sort(w_low) - jnp.sort(w)).max()) > 1e-4


# -- the chunked scan -----------------------------------------------------------
def _scan_operands(t, seed=0, heads=4, p=8, groups=2, n=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (2, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, t, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (2, t, groups, n))
    c = jax.random.normal(keys[4], (2, t, groups, n))
    return x, dt, a, b, c


@pytest.mark.parametrize("t,chunk", [(16, 8), (24, 8), (20, 8), (7, 8),
                                     (33, 16), (8, 8), (64, 128)])
def test_the_chunked_scan_is_the_step_recurrence(t, chunk):
    """Lengths that are and are not multiples of the chunk, one chunk and
    many, shorter than a chunk: forward and every operand's gradient against
    the reference's one-position-after-another scan. 2e-5 of the largest
    value is float32 summation order (the decays differ by a cumulative sum
    against a running product)."""
    ops = _scan_operands(t)
    cot = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def run(fn):
        out, vjp = jax.vjp(fn, *ops)
        return out, vjp(cot)

    with jax.default_matmul_precision("highest"):
        got, got_grads = run(lambda *o: ssd_scan(*o, chunk))
        want, want_grads = run(REF.recurrence)
    assert got.shape == want.shape == ops[0].shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * scale)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * float(jnp.abs(w).max()))


def _steps(x, dt, log_decay, b, c):
    """The recurrence by a Python loop, the decay's exponent given apart
    from the time step that scales the input."""
    rep = x.shape[2] // b.shape[2]
    state = jnp.zeros((*x.shape[:1], *x.shape[2:], b.shape[-1]))
    ys = []
    for t in range(x.shape[1]):
        bt, ct = (jnp.repeat(v[:, t], rep, axis=1) for v in (b, c))
        state = (jnp.exp(log_decay[:, t])[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None])
        ys.append(jnp.sum(state * ct[:, :, None], axis=-1))
    return jnp.stack(ys, axis=1)


@pytest.mark.parametrize("fault", ["none", "a decay applied a position late",
                                   "a bfloat16 decay"])
def test_the_scans_tolerance_refuses(fault):
    """What 2e-5 is for: the recurrence with its decay taken from the
    position before (``S_t = exp(dt_{t-1} a) S_{t-1} + ...``) and with the
    exponent ``dt a`` rounded to bfloat16 are both far outside it, and the
    loop without a fault is inside."""
    x, dt, a, b, c = _scan_operands(24)
    log_decay = dt * a
    if fault == "a bfloat16 decay":
        log_decay = log_decay.astype(jnp.bfloat16).astype(jnp.float32)
    elif fault != "none":
        log_decay = jnp.pad(log_decay, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    with jax.default_matmul_precision("highest"):
        want = ssd_scan(x, dt, a, b, c, 8)
        got = _steps(x, dt, log_decay, b, c)
    err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    assert (err < 2e-5) if fault == "none" else (err > 10 * 2e-5), err


# -- the whole model ---------------------------------------------------------
@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_forward_and_adapter_gradients_match_the_reference(seeded, attention,
                                                           remat):
    """Logits, loss and every adapter's gradient. 3e-5 absolute / 2e-4
    relative is float32 summation order through six blocks (the same the
    two other decoder families are held to); a bfloat16 router or decay is
    1e-3 and more (the tests above)."""
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
    # two projections in each of two Mamba-2 blocks, four in the attention
    # block, the shared expert's two in each of three expert blocks, two
    # leaves each, and the head
    assert len(names) == 2 * (2 * 2 + 4 + 3 * 2) + 1
    for k in sorted(names):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref_grad[k]),
                                   atol=3e-5, rtol=2e-4, err_msg=k)
    # the router's input trains what lies before it: block 1's combine
    # weights carry gradient into block 0's adapters
    assert float(jnp.abs(got["layers_0/mixer/out_proj/lora_b"]).max()) > 0


def test_the_attention_block_reads_grouped_heads_lane_indexed():
    """At the published head width the flash entry takes 32 query heads over
    2 key/value heads where the projections hold them: lane-indexed, no
    ``repeat`` of the key/value heads; a narrow head stays transposed and is
    repeated first."""
    cfg = dict(CFG, num_attention_heads=32, hybrid_override_pattern="*")
    fn = functools.partial(flash_attention, causal=True, block_q=128,
                           block_k=128)
    gauges = _module(cfg, attention_fn=fn, remat=True).build_gauges((1, 256),
                                                                    4)
    assert (gauges["flash_calls_lane_indexed"],
            gauges["flash_calls_transposed"]) == (1, 0)
    assert gauges["remat_saved_names"] == 2
    narrow = _module(dict(cfg, head_dim=64), attention_fn=fn)
    g = narrow.build_gauges((1, 256), 4)
    assert (g["flash_calls_lane_indexed"], g["flash_calls_transposed"]) == (
        0, 1)
    module = _module(cfg, attention_fn=fn)
    x = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
    u = jax.ShapeDtypeStruct((1, 256, 32), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, u, m: nh.gqa_attention(
        p, u, m, module.dims))(params["layers_0"]["mixer"], u, mask).jaxpr
    # nothing copies an array with all 32 heads in it, or the 2 key/value
    # heads out to them, on the way to the call
    moved = [(e.primitive.name, tuple(e.outvars[0].aval.shape))
             for e in eqns(jaxpr)
             if e.primitive.name in ("transpose", "broadcast_in_dim",
                                     "concatenate", "gather", "pad")
             and (32 in e.outvars[0].aval.shape
                  or 32 * 128 in e.outvars[0].aval.shape)]
    assert moved == [], moved
    assert sum(e.primitive.name == "pallas_call" for e in eqns(jaxpr)) == 1


def test_build_gauges_state_the_static_facts():
    gauges = _module(attention_fn=FLASH, remat=True).build_gauges((1, 20), 4)
    assert {k: gauges[k] for k in (
        "ssd_chunks", "ssd_heads", "moe_experts_held", "moe_router_width",
        "moe_top_k")} == {"ssd_chunks": 3, "ssd_heads": 4,
                          "moe_experts_held": 8, "moe_router_width": 40,
                          "moe_top_k": 6}
    # how the held rows travel (``routed.routed_gauges``): at 80 tokens a
    # chunk is one tile and each of the 8 held experts has a chunk of its
    # own; at the hybrid cell's shape (4 x 2,048 tokens, the 22 best of 512
    # with 16 held) 352 rows an expert are two tiles, one chunk of 512
    assert {k: gauges[k] for k in (
        "moe_tile_rows", "moe_chunk_rows", "moe_row_moves_per_pass")} == {
            "moe_tile_rows": 256, "moe_chunk_rows": 256,
            "moe_row_moves_per_pass": 16}
    at_cell = _module(dict(CFG, router_width=512, n_routed_experts=16,
                           num_experts_per_tok=22)).build_gauges((1, 2048), 4)
    assert (at_cell["moe_chunk_rows"],
            at_cell["moe_row_moves_per_pass"]) == (512, 32)
    # the attention block's run traces its call once; out and lse are kept
    assert gauges["flash_calls_lane_indexed"] == 1
    assert gauges["remat_saved_names"] == 2
    assert _module().build_gauges((1, 20), 4)["remat_saved_names"] == 0


# the state-space widths on the 128-lane tiles: the scan's fused path
ON_TILES = dict(CFG, mamba_head_dim=64, ssm_state_size=128, chunk_size=128)


def test_build_gauges_say_which_path_the_scans_take():
    """Traced call sites, as the flash calls' are counted: the toy widths'
    one run of ``ME`` units traces its scan once, on the ``jnp`` path; widths
    on the tiles take the Mosaic calls at every site (a run of two like
    blocks, and two blocks on their own)."""
    toy = _module(remat=True).build_gauges((1, 20), 4)
    assert (toy["ssd_calls_fused"], toy["ssd_calls_xla"]) == (0, 1)
    wide = _module(dict(ON_TILES, hybrid_override_pattern="MMEM*M"),
                   remat=True).build_gauges((1, 20), 4)
    assert (wide["ssd_calls_fused"], wide["ssd_calls_xla"]) == (3, 0)


@pytest.mark.parametrize("remat", [False, True])
def test_the_model_on_the_tiles_is_the_model_off_them(monkeypatch, remat):
    """The whole model at widths that take the scan's Mosaic calls (the
    interpreter here) against itself with the ``jnp`` form in their place:
    logits and every adapter's gradient under the engine's ``vmap`` over
    clients, to float32 summation order."""
    tree = build.nest(_weights(ON_TILES, 11))
    module = _module(ON_TILES, remat=remat)
    x = jnp.asarray(np.random.default_rng(1).integers(
        1, CFG["vocab_size"], (2, 1, 20)), jnp.int32)
    per_client, shared = ptu.split_by_path(tree, module.per_client_param)
    clients = jax.tree_util.tree_map(lambda v: jnp.stack([v, 0.5 * v]),
                                     per_client)

    def run():
        forward = module.bind_shared(shared)

        def loss(p):
            logits = jax.vmap(lambda p, x: forward(p, x)[0]["prediction"])(
                p, x)
            return jnp.sum(jax.nn.log_softmax(logits)[..., 0]), logits

        with jax.default_matmul_precision("highest"):
            (_, logits), grads = jax.value_and_grad(loss, has_aux=True)(
                clients)
        return logits, build.flatten(grads)

    logits, grads = run()
    monkeypatch.setattr(nh, "ssd_scan", ssd_scan_xla)
    want_logits, want = run()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=3e-5, rtol=3e-5)
    assert float(jnp.abs(want["layers_0/mixer/in_proj/lora_a"]).max()) > 0
    for k in sorted(want):
        np.testing.assert_allclose(np.asarray(grads[k]), np.asarray(want[k]),
                                   atol=3e-5, rtol=2e-4, err_msg=k)


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
    # matmul operands in the compute type; the router, the conv's taps, the
    # norms, A_log / D / dt_bias and the embedding stay float32
    assert dtypes["runs/0/0/mixer/in_proj/kernel"] == "bfloat16"
    assert dtypes["runs/0/1/mixer/experts_3/up_proj/kernel"] == "bfloat16"
    assert dtypes["runs/0/1/mixer/fc2_latent_proj/kernel"] == "bfloat16"
    for name in ("runs/0/1/mixer/gate/kernel", "runs/0/0/mixer/A_log",
                 "runs/0/1/mixer/gate/e_score_correction_bias",
                 "runs/0/0/mixer/conv1d/kernel", "runs/0/0/mixer/dt_bias",
                 "runs/0/0/mixer/D", "runs/0/0/mixer/norm/scale",
                 "embed_tokens/embedding"):
        assert dtypes[name] == "float32", name
    # the unit ME repeats twice: one stack of two under each leaf
    assert prepared["runs/0/0/mixer/in_proj/kernel"].shape[0] == 2
    out = module.bind_shared(shared)(per_client, x)[0]["prediction"]
    assert out.shape == (3, 4) and out.dtype == jnp.float32
    # bfloat16 compute on float32 masters stays near the float32 forward
    # (0.251 on this draw of the toy, where 24 of 40 experts are held and a
    # flipped pick moves a token's stream; logits span several units)
    want = _module().apply({"params": tree}, x)[0]["prediction"]
    assert float(jnp.max(jnp.abs(out - want))) < 0.4
    assert model.bind_shared is not None and model.build_gauges is not None


def test_experts_outside_the_router_and_unknown_blocks_are_refused():
    with pytest.raises(ValueError, match="not among the router's 40"):
        _module(dict(CFG, first_expert_held=36)).dims
    with pytest.raises(ValueError, match="a block is one of"):
        _module(dict(CFG, hybrid_override_pattern="MXE")).dims


# -- the shares add up ---------------------------------------------------------
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Five shares of eight experts each, all routing over the same 40: each
    share's routed part through the latent up-projection, plus the shared
    expert counted once, is the reference's UNCUT layer (40 held). No share
    stands in for an absent one."""
    uncut = dict(CFG, n_routed_experts=40, first_expert_held=0,
                 num_hidden_layers=2)
    flat = _weights(uncut, 5)
    p = {k[len("layers_1/"):]: v for k, v in flat.items()
         if k.startswith("layers_1/")}
    s = REF.sizes(uncut, JOB)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 32))
    with jax.default_matmul_precision("highest"):
        want = REF._moe(p, u, s, NM)
        mixer = build.nest(p)["mixer"]
        routed = jnp.zeros_like(want)
        for first in range(0, 40, 8):
            cfg = dict(uncut, n_routed_experts=8, first_expert_held=first)
            share = dict(mixer, **{f"experts_{j}": mixer[f"experts_{first + j}"]
                                   for j in range(8)})
            dims = _module(cfg).dims
            both = nh.latent_moe(share, u, jnp.ones(u.shape[:2]), dims)
            shared_only = nh.relu2_mlp(share["shared_experts"], u, dims)
            routed = routed + (both - shared_only)
        got = routed + nh.relu2_mlp(mixer["shared_experts"], u, dims)
    # float32 summation order over 40 experts in five partial sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=2e-4)
    # and one share alone is not the layer
    assert float(jnp.abs(both - want).max()) > 1e-2


def test_a_pad_position_picks_no_expert_and_the_tokens_are_untouched():
    """Pad positions (the tail) go through the shared expert alone: no held
    expert's tile holds them, so the routed work follows the tokens and not
    the draw's padding; a token's output is what it is without the mask."""
    flat = _weights(CFG, 7)
    p = build.nest({k[len("layers_1/"):]: v for k, v in flat.items()
                    if k.startswith("layers_1/")})["mixer"]
    dims = _module().dims
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 32))
    mask = (jnp.arange(12)[None, :] < jnp.asarray([[12], [7]])).astype(
        jnp.float32)
    with jax.default_matmul_precision("highest"):
        masked = nh.latent_moe(p, u, mask, dims)
        plain = nh.latent_moe(p, u, jnp.ones((2, 12)), dims)
        shared = nh.relu2_mlp(p["shared_experts"], u, dims)
    np.testing.assert_allclose(np.asarray(masked[0]), np.asarray(plain[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(masked[1, :7]),
                               np.asarray(plain[1, :7]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(masked[1, 7:]),
                               np.asarray(shared[1, 7:]), atol=1e-6)
    # and the routed part was something there before the mask
    assert float(jnp.abs(plain[1, 7:] - shared[1, 7:]).max()) > 1e-3


def test_relu2_experts_square_after_the_relu():
    from fl4health_tpu.models.routed import relu2_expert

    x = jnp.asarray([[1.0, -2.0]])
    up = jnp.asarray([[1.0, 1.0], [0.5, -1.0]])
    down = jnp.asarray([[1.0], [10.0]])
    # x up = [0, 3]; relu^2 = [0, 9]; down -> 90
    assert float(relu2_expert(x, up, down)[0, 0]) == 90.0
    dims = _module().dims
    p = {"up_proj": {"kernel": up}, "down_proj": {"kernel": down}}
    assert float(nh.relu2_mlp(p, x, dims)[0, 0]) == 90.0
