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
                                                  _channel_block,
                                                  selective_scan,
                                                  selective_scan_reference)

D_INNER, D_STATE = 24, 4
NAMES = ("x", "dt", "a", "b", "c", "d", "z")


# clients batch x, dt, b, c, z; a and d are shared
CLIENT_AXES = (0, 0, None, 0, 0, None, 0)


def _operands(key, lead, t, dtype=jnp.float32, d_inner=D_INNER,
              d_state=D_STATE):
    ks = jax.random.split(key, 7)
    n = jax.random.normal
    x = n(ks[0], (*lead, t, d_inner)).astype(dtype)
    dt = jax.nn.softplus(n(ks[1], (*lead, t, d_inner))).astype(dtype)
    a = -jnp.exp(0.3 * n(ks[2], (d_inner, d_state)))
    b, c = n(ks[3], (*lead, t, d_state)), n(ks[4], (*lead, t, d_state))
    d, z = n(ks[5], (d_inner,)), n(ks[6], (*lead, t, d_inner)).astype(dtype)
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
        if "vmap" in wrap:
            fn = jax.vmap(fn, in_axes=CLIENT_AXES)
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
    of B and C against every 128-lane chunk, dB and dC folded over chunks);
    each of these widths is ONE channel block of two or three chunks."""
    ops = _operands(jax.random.PRNGKey(d_inner), (2,), 24, d_inner=d_inner,
                    d_state=d_state)
    _close(_scan_at(*ops, block_t=8, unroll=8),
           selective_scan_reference(*ops))
    got = jax.grad(lambda *a: jnp.sum(_scan_at(*a, block_t=8, unroll=2)
                                      ** 2), argnums=tuple(range(7)))(*ops)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) ** 2),
                    argnums=tuple(range(7)))(*ops)
    for name, g, w in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-3,
                                   rtol=2e-4, err_msg=name)


# d_inner -> channel blocks: 1,152 = 3 x 384 (three lane chunks a block),
# 896 = 7 x 128. 384 alone is ONE block of three chunks, as 256 is one of two.
@pytest.mark.parametrize("d_inner,blocks", [(1152, 3), (896, 7)])
def test_several_channel_blocks_and_time_blocks_at_once(d_inner, blocks):
    """Channels innermost on the grid: every channel block's state waits in
    VMEM while the others step through the same time block, dB and dC sum
    over the channel blocks of a time block, dA and dD come round once a time
    block. Three time blocks x three or seven channel blocks x two sequences
    x three clients of different inputs, under vmap + checkpoint: a state or
    an accumulator that leaks into the next sequence, channel block or time
    block shows in the forward or in a gradient."""
    d_state, t, block_t = 4, 24, 8
    assert d_inner // _channel_block(d_inner, True) == blocks
    ops = _operands(jax.random.PRNGKey(d_inner), (3, 2), t, d_inner=d_inner,
                    d_state=d_state)
    weight = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def lift(fn):
        fn = jax.vmap(jax.checkpoint(fn), in_axes=CLIENT_AXES)
        return lambda *args: jnp.sum(fn(*args) * weight)

    def blocked(*args):
        return _scan_at(*args, block_t=block_t, unroll=4)

    _close(jax.vmap(blocked, in_axes=CLIENT_AXES)(*ops),
           jax.vmap(selective_scan_reference, in_axes=CLIENT_AXES)(*ops),
           tol=1e-4)
    got = jax.grad(lift(blocked), argnums=tuple(range(7)))(*ops)
    want = jax.grad(lift(selective_scan_reference),
                    argnums=tuple(range(7)))(*ops)
    for name, g, w in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-3,
                                   rtol=2e-4, err_msg=name)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_no_output_of_the_backward_call_has_a_channel_block_axis():
    """What the backward call writes does not grow with the number of channel
    blocks: the per-lane partial sums of dB and dC are [B, T, d_state, 128],
    B's own lane-splat shape, whatever d_inner / block is (seven here)."""
    d_inner, d_state, t = 896, 4, 24
    ops = _operands(jax.random.PRNGKey(2), (2,), t, d_inner=d_inner,
                    d_state=d_state)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(_scan_at(*a, block_t=8, unroll=4)),
        argnums=tuple(range(7))))(*ops)
    (bwd,) = [e for e in _pallas_calls(jaxpr.jaxpr)
              if e.params["name"] == "ssm_scan_bwd"]
    shapes = [v.aval.shape for v in bwd.outvars]
    seq, part = (2, t, d_inner), (2, t, d_state, 128)
    assert shapes == [seq, seq, seq, part, part, (2, d_state, d_inner),
                      (2, 1, d_inner)]


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
