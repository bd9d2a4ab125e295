"""``models/deepseek.py routed_layer``: ONE implementation of the plan, the
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
BODIES = {"swiglu (three matrices)": (ds.swiglu_expert, 3),
          "relu2 (two matrices)": (ds.relu2_expert, 2)}


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
        return ds.routed_layer(x, u, router, experts, FIRST, score, fn)

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
    one = functools.partial(ds.routed_experts, experts=experts,
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
    grads = jax.grad(lambda e: jnp.sum(ds.routed_experts(
        x, idx, w, e, 0, ds.relu2_expert)))(experts)
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
        idx, w = ds.sigmoid_route(router, u, top_k, 2.826)
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
        return ds.routed_layer(x[:, :D_ROWS], x, router, experts, first, rule,
                               ds.swiglu_expert)

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
