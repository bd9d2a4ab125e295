"""The chunked selective scan against one sequential float32 scan over the
positions: forward and every gradient, under ``vmap`` (the engine's clients
axis) and ``jax.checkpoint`` (a rematerialised layer), at lengths that are
and are not a multiple of the chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.selective_scan import (BLOCK_T, SCOPE, UNROLL,
                                                  _blocked_scan,
                                                  selective_scan,
                                                  selective_scan_reference)

D_INNER, D_STATE = 24, 4
NAMES = ("x", "dt", "a", "b", "c", "d", "z")


def _operands(key, lead, t, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    n = jax.random.normal
    x = n(ks[0], (*lead, t, D_INNER)).astype(dtype)
    dt = jax.nn.softplus(n(ks[1], (*lead, t, D_INNER))).astype(dtype)
    a = -jnp.exp(0.3 * n(ks[2], (D_INNER, D_STATE)))
    b, c = n(ks[3], (*lead, t, D_STATE)), n(ks[4], (*lead, t, D_STATE))
    d, z = n(ks[5], (D_INNER,)), n(ks[6], (*lead, t, D_INNER)).astype(dtype)
    return x, dt, a, b, c, d, z


def _scan_at(*ops, block_t, unroll=None, interpret=True):
    """The scan at a time block of the test's own (the public function has
    one, sized for the chip)."""
    return _blocked_scan(*ops, block_t, min(unroll or block_t, block_t),
                         interpret)


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("t,chunk", [(32, 8), (37, 8), (5, 8), (64, 64), (50, 16)])
def test_forward_matches_the_sequential_scan(t, chunk):
    ops = _operands(jax.random.PRNGKey(t), (2,), t)
    _close(_scan_at(*ops, block_t=chunk, unroll=4),
           selective_scan_reference(*ops))


@pytest.mark.parametrize("t,chunk", [(32, 8), (37, 8), (21, 16)])
@pytest.mark.parametrize("wrap", ["plain", "checkpoint", "vmap",
                                  "vmap_checkpoint"])
def test_every_gradient_matches_under_vmap_and_checkpoint(t, chunk, wrap):
    lead = (3, 2) if "vmap" in wrap else (2,)
    ops = _operands(jax.random.PRNGKey(100 + t), lead, t)
    weight = jax.random.normal(jax.random.PRNGKey(7), ops[0].shape)

    def chunked(*args):
        return _scan_at(*args, block_t=chunk, unroll=4)

    def lift(fn):
        if "checkpoint" in wrap:
            fn = jax.checkpoint(fn)
        if "vmap" in wrap:  # clients batch x, dt, b, c, z; a and d are shared
            fn = jax.vmap(fn, in_axes=(0, 0, None, 0, 0, None, 0))
        return lambda *args: jnp.sum(fn(*args) * weight)

    got = jax.grad(lift(chunked), argnums=tuple(range(7)))(*ops)
    want = jax.grad(lift(selective_scan_reference),
                    argnums=tuple(range(7)))(*ops)
    for name, g, w in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("d_inner,d_state", [(256, 16), (384, 8)])
def test_channel_blocks_of_several_lane_chunks(d_inner, d_state):
    """d_inner a multiple of 128: the TPU's blocking (one [d_state, 128] tile
    of B and C against every 128-lane chunk, dB and dC folded over chunks)."""
    ks = jax.random.split(jax.random.PRNGKey(d_inner), 7)
    n = jax.random.normal
    ops = (n(ks[0], (2, 24, d_inner)),
           jax.nn.softplus(n(ks[1], (2, 24, d_inner))),
           -jnp.exp(0.3 * n(ks[2], (d_inner, d_state))),
           n(ks[3], (2, 24, d_state)), n(ks[4], (2, 24, d_state)),
           n(ks[5], (d_inner,)), n(ks[6], (2, 24, d_inner)))
    _close(_scan_at(*ops, block_t=8, unroll=8),
           selective_scan_reference(*ops))
    got = jax.grad(lambda *a: jnp.sum(_scan_at(*a, block_t=8, unroll=2)
                                      ** 2), argnums=tuple(range(7)))(*ops)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) ** 2),
                    argnums=tuple(range(7)))(*ops)
    for name, g, w in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-3,
                                   rtol=2e-4, err_msg=name)


def test_the_compiled_kernel_refuses_a_width_off_the_lanes():
    ops = _operands(jax.random.PRNGKey(1), (1,), 16)
    with pytest.raises(ValueError, match="128-lane"):
        _scan_at(*ops, block_t=8, interpret=False)


def test_bfloat16_operands_keep_a_float32_state():
    ops32 = _operands(jax.random.PRNGKey(3), (2,), 40)
    ops16 = _operands(jax.random.PRNGKey(3), (2,), 40, jnp.bfloat16)
    out = _scan_at(*ops16, block_t=16)
    assert out.dtype == jnp.bfloat16
    # against the float32 scan of the SAME rounded operands: only the final
    # cast separates them, so the recurrence did not run in bfloat16
    _close(out, selective_scan_reference(*ops16), tol=2e-2)
    assert float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - selective_scan_reference(*ops32)))) < 0.5


def test_only_chunk_boundary_states_are_saved_for_the_backward_pass():
    """The residuals of the outer scan hold [T / chunk] states, never [T]."""
    t, chunk = 64, 8
    ops = _operands(jax.random.PRNGKey(5), (1,), t)
    _, vjp = jax.vjp(lambda *a: _scan_at(*a, block_t=chunk), *ops)
    sizes = [leaf.size for leaf in jax.tree_util.tree_leaves(vjp)
             if hasattr(leaf, "size")]
    whole_state = t * D_INNER * D_STATE
    assert max(sizes) < whole_state
    assert (t // chunk) * D_INNER * D_STATE in sizes


def test_every_op_carries_the_scope_a_trace_reads():
    ops = _operands(jax.random.PRNGKey(6), (1,), 16)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(_scan_at(*a, block_t=8)))
                   ).lower(*ops).as_text(debug_info=True)
    assert SCOPE == "fl_layer::ssm_scan" and SCOPE in text
    assert f"transpose(jvp({SCOPE}))" in text or f"jvp({SCOPE})" in text


def test_the_public_scan_is_the_blocked_one_at_the_chips_block():
    """No knob on the path: one time block, padded to (T = 70 is 64 + 6)."""
    assert BLOCK_T % UNROLL == 0
    ops = _operands(jax.random.PRNGKey(8), (1,), 70)
    _close(selective_scan(*ops), selective_scan_reference(*ops))
    _close(selective_scan(*ops), _scan_at(*ops, block_t=BLOCK_T, unroll=UNROLL),
           tol=0)
