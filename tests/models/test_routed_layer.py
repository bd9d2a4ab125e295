"""``models/routed.py routed_layer``: ONE implementation of the plan, the
tiles, the client fold and the frozen-expert VJP under both families'
scoring rules (softmax, group-limited, unnormalised / sigmoid, a selection
bias, renormalised and scaled) and both expert bodies (a SwiGLU triple / two
matrices with ``relu^2``): forward, the rows' gradient and the gradient
through the combine weights into the router's input, against every held
expert over every token by a plain loop."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.models import deepseek as ds
from fl4health_tpu.models import routed as rt
from fl4health_tpu.models import nemotron_h as nh

N, D_ROUTER, D_ROWS, F, WIDTH, HELD, FIRST, TOP_K = 48, 12, 8, 10, 16, 6, 4, 5


def _softmax_rule():
    dims = type("Dims", (), dict(n_group=4, topk_group=2, top_k=TOP_K,
                                 routed_scale=3.0))()
    return lambda router, u: ds.route(router, u, dims), (4, D_ROUTER, 4)


def _sigmoid_rule():
    return (lambda router, u: nh.sigmoid_route(router, u, TOP_K, 5.0),
            (D_ROUTER, WIDTH))


RULES = {"softmax, group-limited, unnormalised": _softmax_rule,
         "sigmoid, selection bias, renormalised": _sigmoid_rule}
BODIES = {"swiglu (three matrices)": (rt.swiglu_expert, 3),
          "relu2 (two matrices)": (rt.relu2_expert, 2)}


def _plain_body(name, x, mats):
    if name.startswith("swiglu"):
        gate, up, down = mats
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down
    up, down = mats
    return jnp.square(jax.nn.relu(x @ up)) @ down


@pytest.mark.parametrize("body", sorted(BODIES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_both_rules_and_both_bodies_through_one_implementation(rule, body):
    score, kernel_shape = RULES[rule]()
    fn, n_mats = BODIES[body]
    keys = iter(jax.random.split(jax.random.PRNGKey(len(rule) + len(body)),
                                 64))
    router = {"kernel": jax.random.normal(next(keys), kernel_shape) / 3,
              "e_score_correction_bias": 0.05 * jax.random.normal(
                  next(keys), (WIDTH,))}
    shapes = [(D_ROWS, F)] * (n_mats - 1) + [(F, D_ROWS)]
    experts = [tuple(jax.random.normal(next(keys), s) / 3 for s in shapes)
               for _ in range(HELD)]
    u = jax.random.normal(next(keys), (N, D_ROUTER))
    x = jax.random.normal(next(keys), (N, D_ROWS))

    def program(x, u):
        return rt.routed_layer(x, u, router, experts, FIRST, score, fn)

    def plain(x, u):
        idx, w = score(router, u)
        y = jnp.zeros((N, D_ROWS))
        for j, mats in enumerate(experts):
            combine = jnp.sum(jnp.where(idx == FIRST + j, w, 0.0), axis=1)
            y = y + combine[:, None] * _plain_body(body, x, mats)
        return y

    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(program, x, u)
        want, want_vjp = jax.vjp(plain, x, u)
        cot = jax.random.normal(next(keys), got.shape)
        grads, want_grads = vjp(cot), want_vjp(cot)
    assert got.dtype == jnp.float32 and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for name, g, w in zip(("rows", "router input"), grads, want_grads):
        assert float(jnp.abs(w).max()) > 1e-3, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_client_fold_is_vmaps_mathematics_for_either_body(body):
    fn, n_mats = BODIES[body]
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    shapes = [(D_ROWS, F)] * (n_mats - 1) + [(F, D_ROWS)]
    experts = [tuple(jax.random.normal(next(keys), s) / 3 for s in shapes)
               for _ in range(HELD)]
    x = jax.random.normal(next(keys), (3, N, D_ROWS))
    idx = jax.random.randint(next(keys), (3, N, TOP_K), 0, WIDTH)
    w = jax.random.uniform(next(keys), (3, N, TOP_K))
    one = functools.partial(rt.routed_experts, experts=experts,
                            first_expert_held=FIRST, body=fn)
    got = jax.vmap(lambda x, i, w: one(x, i, w))(x, idx, w)
    want = jnp.stack([one(x[c], idx[c], w[c]) for c in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_experts_get_no_gradient_whatever_their_body():
    """A frozen base: the VJP gives the rows' and the weights' gradients and
    none for an expert's matrices."""
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
    experts = [(jax.random.normal(next(keys), (D_ROWS, F)),
                jax.random.normal(next(keys), (F, D_ROWS)))
               for _ in range(2)]
    x = jax.random.normal(next(keys), (N, D_ROWS))
    idx = jax.random.randint(next(keys), (N, 2), 0, 2)
    w = jnp.ones((N, 2))
    grads = jax.grad(lambda e: jnp.sum(rt.routed_experts(
        x, idx, w, e, 0, rt.relu2_expert)))(experts)
    assert all(float(jnp.abs(m).max()) == 0.0 for pair in grads for m in pair)


def test_the_sigmoid_rule_at_eight_of_128_with_a_share_and_pads():
    """The rule at the afmoe family's numbers (``models/afmoe.py``: a
    128-wide router, the 8 largest of ``s + b``, renormalised, times 2.826)
    through the one implementation, 16 experts held from the 32nd, the pad
    positions picking none (index -1: no tile holds them): forward and both
    gradients against every held expert over every token by a plain loop."""
    width, top_k, held, first = 128, 8, 16, 32
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 64))
    router = {"kernel": jax.random.normal(next(keys), (D_ROUTER, width)) / 3,
              "e_score_correction_bias": 0.05 * jax.random.normal(
                  next(keys), (width,))}
    experts = [tuple(jax.random.normal(next(keys), s) / 3
                     for s in [(D_ROWS, F)] * 2 + [(F, D_ROWS)])
               for _ in range(held)]
    u = jax.random.normal(next(keys), (N, D_ROUTER))
    live = (jnp.arange(N) < N - 7)[:, None]

    def rule(router, u):
        idx, w = rt.sigmoid_route(router, u, top_k, 2.826)
        return jnp.where(live, idx, -1), w

    def plain(x):
        idx, w = rule(router, x)
        y = jnp.zeros((N, D_ROWS))
        for j, mats in enumerate(experts):
            combine = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=1)
            y = y + combine[:, None] * _plain_body("swiglu", x[:, :D_ROWS],
                                                   mats)
        return y

    def program(x):
        return rt.routed_layer(x[:, :D_ROWS], x, router, experts, first, rule,
                               rt.swiglu_expert)

    with jax.default_matmul_precision("highest"):
        idx, w = rule(router, u)
        got, vjp = jax.vjp(program, u)
        want, want_vjp = jax.vjp(plain, u)
        cot = jax.random.normal(next(keys), got.shape)
        (grad,), (want_grad,) = vjp(cot), want_vjp(cot)
    assert idx.shape == (N, top_k)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.826, rtol=1e-5)
    # the share sees some of the picks and not all (8 * 16 / 128 = 1 a token
    # expected), and a pad position none
    mine = (idx >= first) & (idx < first + held)
    assert 0 < int(mine.sum()) < mine.size // 2
    assert float(jnp.abs(got[N - 7:]).max()) == 0.0
    assert float(jnp.abs(want[:N - 7]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad),
                               atol=5e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# How the held rows travel (PR 45): an expert's tiles in chunks, one gather
# and one combine a chunk, the tiles on contiguous slices
# ---------------------------------------------------------------------------

TILE, CHUNK, MANY = 4, 16, 256


@pytest.fixture
def small_chunks(monkeypatch):
    """Tiles of 4 rows and chunks of 16 (four tiles), so that an expert's
    share of 256 tokens is several chunks; the cached ``custom_vjp`` closes over nothing
    of the constants, but clear it on both sides all the same."""
    monkeypatch.setattr(rt, "TILE_ROWS", TILE)
    monkeypatch.setattr(rt, "CHUNK_ROWS", CHUNK)
    rt._routed_fn.cache_clear()
    yield
    rt._routed_fn.cache_clear()


def _held_everywhere(idx):
    return idx


def _held_nowhere(idx):
    return jnp.where((idx >= FIRST) & (idx < FIRST + HELD), 0, idx)


def _pad_tail(idx):
    return jnp.where((jnp.arange(idx.shape[0]) < MANY - 37)[:, None], idx, -1)


# name -> (first held, held, what becomes of the rule's picks)
LOADS = {
    "a share, its rows straddling chunk edges": (FIRST, HELD,
                                                 _held_everywhere),
    "every pair held": (0, WIDTH, _held_everywhere),
    "no pair held": (FIRST, HELD, _held_nowhere),
    "a pad tail picking none": (FIRST, HELD, _pad_tail),
}


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("body", sorted(BODIES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_chunks_of_the_tiles_against_the_plain_loop(
        small_chunks, rule, body, load):
    score, kernel_shape = RULES[rule]()
    fn, n_mats = BODIES[body]
    first, held, picks = LOADS[load]
    keys = iter(jax.random.split(jax.random.PRNGKey(45), 80))
    router = {"kernel": jax.random.normal(next(keys), kernel_shape) / 3,
              "e_score_correction_bias": 0.05 * jax.random.normal(
                  next(keys), (WIDTH,))}
    shapes = [(D_ROWS, F)] * (n_mats - 1) + [(F, D_ROWS)]
    experts = [tuple(jax.random.normal(next(keys), s) / 3 for s in shapes)
               for _ in range(held)]
    u = jax.random.normal(next(keys), (MANY, D_ROUTER))
    x = jax.random.normal(next(keys), (MANY, D_ROWS))

    def routed(router, u):
        idx, w = score(router, u)
        return picks(idx), w

    def program(x, u):
        return rt.routed_layer(x, u, router, experts, first, routed, fn)

    def plain(x, u):
        idx, w = routed(router, u)
        y = jnp.zeros((MANY, D_ROWS))
        for j, mats in enumerate(experts):
            combine = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=1)
            y = y + combine[:, None] * _plain_body(body, x, mats)
        return y

    idx, w = routed(router, u)
    _, _, _, starts, counts = rt._plan(idx, w, first, held)
    pairs = int(counts.sum())
    assert rt._chunk_rows(MANY) == CHUNK
    if load == "no pair held":
        assert pairs == 0
    elif load == "every pair held":
        assert pairs == MANY * TOP_K
    else:
        # every expert needs several chunks, and one ends inside a chunk
        # and inside a tile
        assert int(counts.min()) > 2 * CHUNK
        assert np.any(np.asarray(counts) % CHUNK > TILE)
        assert np.any(np.asarray(counts) % TILE)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(program, x, u)
        want, want_vjp = jax.vjp(plain, x, u)
        cot = jax.random.normal(next(keys), got.shape)
        grads, want_grads = vjp(cot), want_vjp(cot)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if load == "a pad tail picking none":
        assert float(jnp.abs(got[MANY - 37:]).max()) == 0.0
    for name, g, wg in zip(("rows", "router input"), grads, want_grads):
        if pairs:
            assert float(jnp.abs(wg).max()) > 1e-3, name
        else:
            assert float(jnp.abs(g).max()) == 0.0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg), atol=5e-5,
                                   rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_client_fold_over_many_chunks(small_chunks, body):
    """Three clients' tokens are ONE call's rows: 3 x 256 x 5 pairs through
    chunks of 16 equal a loop over the clients, values and both gradients."""
    fn, n_mats = BODIES[body]
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))
    shapes = [(D_ROWS, F)] * (n_mats - 1) + [(F, D_ROWS)]
    experts = [tuple(jax.random.normal(next(keys), s) / 3 for s in shapes)
               for _ in range(HELD)]
    x = jax.random.normal(next(keys), (3, MANY, D_ROWS))
    idx = jnp.argsort(jax.random.uniform(next(keys), (3, MANY, WIDTH)),
                      axis=-1)[..., :TOP_K].astype(jnp.int32)
    w = jax.random.uniform(next(keys), (3, MANY, TOP_K))
    cot = jax.random.normal(next(keys), (3, MANY, D_ROWS))
    one = functools.partial(rt.routed_experts, experts=experts,
                            first_expert_held=FIRST, body=fn)

    def folded(x, w):
        return jax.vmap(lambda x, i, w: one(x, i, w))(x, idx, w)

    def looped(x, w):
        return jnp.stack([one(x[c], idx[c], w[c]) for c in range(3)])

    assert rt._chunk_rows(3 * MANY) == CHUNK
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(folded, x, w)
        want, want_vjp = jax.vjp(looped, x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    for g, wg in zip(vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg), atol=1e-5)


def _loops_around(jaxpr, wanted, inside=0):
    """Loop depth of every equation whose primitive ``wanted(e)`` holds,
    through every nested jaxpr."""
    found = []
    for e in jaxpr.eqns:
        if wanted(e):
            found.append(inside)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns") and e.primitive.name != "pallas_call":
                    found += _loops_around(
                        inner, wanted, inside + (e.primitive.name == "while"))
    return found


@pytest.mark.parametrize("width", [D_ROWS, 128])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_no_tile_moves_a_row_of_the_callers_arrays(body, width):
    """The jaxpr of the forward holds, a held expert, ONE gather from ``x``
    [N, d] and ONE combine into the sum (a scatter-add into [N, d] at a
    narrow width, the ``add_rows`` call into [N, d / 128, 128] at a whole
    tile's), both in the expert's chunk loop (depth 1) and none in a tile
    loop (depth 2); the backward gathers ``x`` and ``dy`` once a chunk and
    combines ``dx`` once. At the real constants."""
    fn, n_mats = BODIES[body]
    n, k, d = 64, 2, width
    shapes = [(d, F)] * (n_mats - 1) + [(F, d)]
    flat = [jnp.ones(s) for _ in range(3) for s in shapes]
    x, w = jnp.ones((n, d)), jnp.ones((n, k))
    idx = jnp.zeros((n, k), jnp.int32)

    def gathers(e):
        return e.primitive.name == "gather" and tuple(
            e.invars[0].aval.shape) == (n, d)

    def combines(e):
        if width % 128:
            return e.primitive.name == "scatter-add" and tuple(
                e.invars[0].aval.shape) == (n, d)
        return e.primitive.name == "pallas_call"

    def whole_array_scatters(e):
        return e.primitive.name.startswith("scatter") and e.invars[
            0].aval.shape[0] == n and e.invars[0].aval.ndim > 1

    fwd = jax.make_jaxpr(functools.partial(rt._routed_fwd, 0, fn, n_mats))(
        x, idx, w, *flat).jaxpr
    bwd = jax.make_jaxpr(functools.partial(rt._routed_bwd, 0, fn, n_mats))(
        x, idx, w, jnp.ones((n, d)), *flat).jaxpr
    # three held experts: a chunk loop each
    assert _loops_around(fwd, gathers) == [1] * 3
    assert _loops_around(fwd, combines) == [1] * 3
    assert _loops_around(bwd, gathers) == [1] * 6
    assert _loops_around(bwd, combines) == [1] * 3
    if width % 128 == 0:
        assert _loops_around(fwd, whole_array_scatters) == []
        assert _loops_around(bwd, whole_array_scatters) == []
    # and the tile loops are there, one inside each expert's chunk loop
    assert sorted(_loops_around(
        fwd, lambda e: e.primitive.name == "while")) == [0] * 3 + [1] * 3


def test_the_gauges_of_how_rows_travel():
    """A chunk is a sixteenth of the tokens in whole tiles, between one tile
    and ``CHUNK_ROWS``; the moves are a gather and a combine a chunk, and an
    expert's expected load is one chunk in each of the three cells."""
    assert (rt.TILE_ROWS, rt.CHUNK_ROWS) == (256, 4096)
    assert [rt._chunk_rows(n) for n in (48, 4096, 8192, 32768, 10 ** 6)
            ] == [256, 256, 512, 2048, 4096]
    # window cell: 2,048 rows an expert are eight tiles, one chunk
    assert rt.routed_gauges(32768, 8, 16, 128) == {
        "moe_tile_rows": 256, "moe_chunk_rows": 2048,
        "moe_row_moves_per_pass": 32}
    # hybrid: 352 rows are two tiles, one chunk; expert: 153 rows one tile
    assert rt.routed_gauges(8192, 22, 16, 512)["moe_row_moves_per_pass"] == 32
    assert rt.routed_gauges(4096, 6, 8, 160)["moe_row_moves_per_pass"] == 16
    # an expert that sees twice its chunk takes two
    assert rt.routed_gauges(32768, 8, 16, 64)["moe_row_moves_per_pass"] == 64
