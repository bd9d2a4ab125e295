"""The fused path of ``kernels/ssd_scan.py`` (two Mosaic calls under a
``custom_vjp``; the interpreter here) at the smallest widths it takes, 4
heads of 64 in 2 groups over a state of 128 and chunks of 128: against the
benchmark's one-position-after-another recurrence, forward and all five
operands' gradients, at lengths that are and are not multiples of the chunk;
against the ``jnp`` form with bfloat16 operands; under ``vmap`` (the
engine's clients axis), ``jax.checkpoint`` and ``grad``; what its tolerances
refuse; which widths take which path; and that no ``[Q, Q]`` tile is an array
of the traced program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import load_module
from fl4health_tpu.kernels import ssd_scan as ssd
from fl4health_tpu.kernels.ssd_scan import (count_call_sites, ssd_scan,
                                            ssd_scan_xla)
from tests.models.remat_probe import eqns, pallas_calls

REF = load_module("reference", "nemotron_h_classifier")
HEADS, P, GROUPS, N, CHUNK = 4, 64, 2, 128, 128
NAMES = ("x", "dt", "a", "b", "c")
# clients batch x, dt, b, c; the decays a are the base's
CLIENT_AXES = (0, 0, None, 0, 0)


def _operands(t, lead=(2,), dtype=jnp.float32, seed=0, heads=HEADS, p=P,
              groups=GROUPS, n=N):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (*lead, t, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (*lead, t, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (*lead, t, groups, n)).astype(dtype)
    c = jax.random.normal(keys[4], (*lead, t, groups, n)).astype(dtype)
    return x, dt, a, b, c


def _with_grads(fn, ops, seed=9):
    cot = jax.random.normal(jax.random.PRNGKey(seed), ops[0].shape)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(fn, *ops)
        return out, vjp(cot)


def _assert_close(got, want, tol):
    """Every array within ``tol`` of ITS largest value."""
    for name, g, w in zip(("y", *("d" + n for n in NAMES)), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, atol=tol * np.abs(w).max(),
                                   err_msg=name)


@pytest.mark.parametrize("t", [128, 200, 384])
def test_the_fused_scan_is_the_step_recurrence(t):
    """One chunk, a length that is no multiple of the chunk, three chunks
    (the state carried in the call's scratch twice): forward and every
    operand's gradient at the tolerance the ``jnp`` form is held to. 2e-5 of
    the largest value is float32 summation order."""
    ops = _operands(t)
    with count_call_sites() as sites:
        got, got_grads = _with_grads(lambda *o: ssd_scan(*o, CHUNK), ops)
    assert sites == {"fused": 1, "xla": 0}
    want, want_grads = _with_grads(REF.recurrence, ops)
    assert got.shape == ops[0].shape and got.dtype == jnp.float32
    _assert_close((got, *got_grads), (want, *want_grads), 2e-5)


@pytest.mark.parametrize("t", [128, 200, 384])
def test_bfloat16_operands_follow_the_jnp_form(t):
    """The casts are the ``jnp`` form's (scores, ``dt x``, the weighted
    inputs and the incoming state to the operands' type, float32 sums), so
    the float32 ``y`` differs by roundings that fell the other way: 1.5e-4 of
    the largest (what that refuses: the test below). The gradients leave in bfloat16, x's and B's and C's: one
    unit in their last place is 0.4-0.8 %, and the CPU's ``jnp`` form keeps a
    float32 cotangent where the MXU (and the call) round it: 2e-2."""
    ops = _operands(t, dtype=jnp.bfloat16)
    got, got_grads = _with_grads(lambda *o: ssd_scan(*o, CHUNK), ops)
    want, want_grads = _with_grads(lambda *o: ssd_scan_xla(*o, CHUNK), ops)
    assert got.dtype == jnp.float32
    assert [g.dtype for g in got_grads] == [w.dtype for w in want_grads]
    _assert_close((got,), (want,), 1.5e-4)
    _assert_close((got, *got_grads), (want, *want_grads), 2e-2)


@pytest.mark.parametrize("wrap", ["checkpoint", "vmap", "vmap_checkpoint"])
def test_every_gradient_matches_under_vmap_and_checkpoint(wrap):
    """The engine's wrapping: clients vmapped over everything but the
    base's decays, the block rematerialised, differentiated."""
    t = 200
    ops = _operands(t, lead=(3, 2) if "vmap" in wrap else (2,))

    def wrapped(fn):
        fn = jax.checkpoint(fn) if "checkpoint" in wrap else fn
        return jax.vmap(fn, in_axes=CLIENT_AXES) if "vmap" in wrap else fn

    got, got_grads = _with_grads(wrapped(lambda *o: ssd_scan(*o, CHUNK)), ops)
    want, want_grads = _with_grads(
        wrapped(lambda *o: ssd_scan_xla(*o, CHUNK)), ops)
    _assert_close((got, *got_grads), (want, *want_grads), 2e-5)


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
def test_the_scans_tolerance_refuses_on_the_fused_path(fault):
    """The two faults ``tests/models/test_nemotron_h.py`` plants against the
    ``jnp`` form, against the calls (two chunks, the second not whole): both
    are far outside 2e-5, the loop without a fault inside."""
    x, dt, a, b, c = _operands(136, lead=(1,))
    log_decay = dt * a
    if fault == "a bfloat16 decay":
        log_decay = log_decay.astype(jnp.bfloat16).astype(jnp.float32)
    elif fault != "none":
        log_decay = jnp.pad(log_decay, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    with jax.default_matmul_precision("highest"):
        want = ssd_scan(x, dt, a, b, c, CHUNK)
        got = _steps(x, dt, log_decay, b, c)
    err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    assert (err < 2e-5) if fault == "none" else (err > 10 * 2e-5), err


@pytest.mark.parametrize("rounded", ["nothing", "a tile's exponent",
                                     "the running sums"])
def test_a_decay_in_the_operands_type_is_refused(monkeypatch, rounded):
    """With bfloat16 operands the decays stay float32, as the ``jnp`` form's
    do: the two differ by roundings of the products' operands that fell the
    other way (3.4e-5 of the largest value at most over three seeds; held to
    1.5e-4), and a call that rounded a tile's exponent ``cum_t - cum_s``, or
    the running sums it reads, to the operands' type is ten times outside
    that (2.2e-3 and 0.35)."""
    ops = _operands(384, dtype=jnp.bfloat16)
    low = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ssd_scan_xla(*ops, CHUNK)
    if rounded == "a tile's exponent":
        real = ssd._decay
        monkeypatch.setattr(ssd, "_decay", lambda cum_t, cum_s, live: real(
            low(cum_t - cum_s), 0.0, live))
    elif rounded == "the running sums":
        real = ssd._fused
        monkeypatch.setattr(ssd, "_fused", lambda x, dt, cum, *rest: real(
            x, dt, low(cum), *rest))
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*ops, CHUNK)
    err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    assert (err < 1.5e-4) if rounded == "nothing" else (err > 1.5e-3), err


@pytest.mark.parametrize("heads,p,groups,n,chunk,path", [
    (4, 64, 2, 128, 128, "fused"),   # the test's own widths
    (2, 128, 2, 128, 128, "fused"),  # a head a lane tile
    (8, 32, 2, 256, 128, "fused"),   # four heads a tile, a wider state
    (4, 8, 2, 16, 8, "xla"),         # tests/models' toy widths
    (4, 64, 2, 128, 64, "xla"),      # a chunk off the tiles
    (4, 64, 2, 64, 128, "xla"),      # a state off the tiles
    (4, 64, 4, 128, 128, "xla"),     # a group of ONE head of 64: half a tile
    (2, 256, 2, 128, 128, "xla"),    # a head wider than a tile
])
def test_the_operands_shapes_pick_the_path(heads, p, groups, n, chunk, path):
    """A rule on ``chunk``, ``N``, ``R * P`` and ``P``, no knob: the traced
    program of an ineligible call IS the ``jnp`` form's, equation for
    equation, and holds no Pallas call; an eligible one holds one."""
    ops = _operands(2 * chunk, heads=heads, p=p, groups=groups, n=n)
    with count_call_sites() as sites:
        jaxpr = jax.make_jaxpr(lambda *o: ssd_scan(*o, chunk))(*ops)
    assert sites == {"fused": int(path == "fused"),
                     "xla": int(path == "xla")}
    assert pallas_calls(jaxpr.jaxpr, "ssd_chunk_fwd") == int(path == "fused")
    if path == "xla":
        assert str(jaxpr) == str(jax.make_jaxpr(
            lambda *o: ssd_scan_xla(*o, chunk))(*ops))


@pytest.mark.parametrize("heads,p,n", [(2, 128, 128), (8, 32, 256)])
def test_other_eligible_widths_agree_with_the_jnp_form(heads, p, n):
    """One head and four heads a 128-lane tile, a state of two tiles."""
    ops = _operands(200, heads=heads, p=p, n=n)
    got, got_grads = _with_grads(lambda *o: ssd_scan(*o, CHUNK), ops)
    want, want_grads = _with_grads(lambda *o: ssd_scan_xla(*o, CHUNK), ops)
    _assert_close((got, *got_grads), (want, *want_grads), 2e-5)


def _tiles(jaxpr, t, lead=2):
    """The results of ``jaxpr``'s equations, outside the calls' own bodies,
    that hold a ``[Q, Q]`` tile a (sequence, chunk, head)."""
    return [(e.primitive.name, v.aval.shape) for e in eqns(jaxpr)
            for v in e.outvars
            if tuple(v.aval.shape[-2:]) == (CHUNK, CHUNK)
            and v.aval.size >= lead * (t // CHUNK) * HEADS * CHUNK * CHUNK]


def test_no_tile_is_an_array_of_the_program():
    """Forward and gradient, outside the calls' own bodies: no equation's
    result has two chunk-position axes (the ``jnp`` form's decay, scores and
    their cotangents are ``[.., Q, Q]`` a head), and the backward's residuals
    are the operands and the states at the chunks' starts."""
    t = 3 * CHUNK
    ops = _operands(t)
    grad = jax.grad(lambda *o: jnp.sum(ssd_scan(*o, CHUNK)),
                    argnums=tuple(range(5)))
    for fn, calls in ((lambda *o: ssd_scan(*o, CHUNK), ("ssd_chunk_fwd",)),
                      (grad, ("ssd_chunk_fwd", "ssd_chunk_bwd"))):
        jaxpr = jax.make_jaxpr(fn)(*ops).jaxpr
        for name in calls:
            assert pallas_calls(jaxpr, name) == 1, name
        assert _tiles(jaxpr, t) == [], _tiles(jaxpr, t)
    tiled = jax.make_jaxpr(lambda *o: ssd_scan_xla(*o, CHUNK))(*ops).jaxpr
    assert _tiles(tiled, t)
    _, vjp = jax.vjp(lambda *o: ssd_scan(*o, CHUNK), *ops)
    sizes = sorted({leaf.size for leaf in jax.tree_util.tree_leaves(vjp)
                    if hasattr(leaf, "size")})
    states = 2 * (t // CHUNK) * HEADS * P * N
    assert sizes[-1] == states, sizes  # x is 2 * t * HEADS * P: a third
    assert 2 * t * HEADS * CHUNK not in sizes  # a head's tiles


def test_every_op_carries_the_scope_a_trace_reads():
    """``ssd_scan_ms_per_round`` sums the ops under ``fl_layer::ssd_scan``,
    and the pass comes from JAX's own markers in the same name stack: the
    backward call's must hold ``transpose(``."""
    ops = _operands(CHUNK)
    text = jax.jit(jax.grad(lambda *o: jnp.sum(ssd_scan(*o, CHUNK)))).lower(
        *ops).as_text(debug_info=True)
    backward = [line for line in text.splitlines() if "ssd_chunk_bwd" in line]
    assert backward and all(
        "transpose(jvp(fl_layer::ssd_scan))" in line for line in backward)
    assert "jvp(fl_layer::ssd_scan)/ssd_chunk_fwd" in text
