"""A sliding window in the three flash calls (``window``, static, beside
``causal``): query i sees key j iff ``0 <= i - j < window`` and j is no pad.
Forward and the three gradients against dense masked attention for windows
below, at and above a block and beyond the sequence, on the lane-indexed
grouped path and the transposed one, with pad masks, under ``vmap`` +
``checkpoint`` + ``grad``; a window as long as the sequence is ``causal``;
tiles wholly behind the window are not executed (counted, and shown by a
NaN that no executed tile may touch); and without a ``window`` the four call
shapes of the accepted cells trace to the parent's program."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (count_call_sites,
                                                   flash_attention,
                                                   flash_attention_lse,
                                                   live_tiles)

B, T, BLOCK = 2, 80, 16
MASK = (jnp.arange(T)[None, :] < jnp.asarray([[T], [57]])).astype(jnp.float32)
# below a block, at it, between two, and beyond the sequence
WINDOWS = (1, BLOCK - 1, BLOCK, 3 * BLOCK // 2, T + 5)
# name -> (query heads, key/value heads, head width, the path it takes)
LAYOUTS = {
    "lane-indexed, 4 heads over 2 grouped heads of 128": (4, 2, 128,
                                                          "lane_indexed"),
    "transposed, 3 heads of 24": (3, 3, 24, "transposed"),
}


def _operands(h, kv, d, dtype=jnp.float32, b=B, t=T):
    keys = jax.random.split(jax.random.PRNGKey(h * d), 4)
    draw = lambda key, n: jax.random.normal(key, (b, t, n, d)).astype(dtype)  # noqa: E731
    return (draw(keys[0], h), draw(keys[1], kv), draw(keys[2], kv),
            draw(keys[3], h))


def _dense(q, k, v, mask, window=None):
    """Causal attention with every score materialised; under ``window`` the
    query's own position and the ``window - 1`` before it."""
    rep, t = q.shape[2] // k.shape[2], q.shape[1]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (mask[:, None, None, :] > 0) & (j <= i)
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("which", sorted(LAYOUTS))
def test_window_matches_dense_masked_attention(which, window):
    h, kv, d, path = LAYOUTS[which]
    q, k, v, cot = _operands(h, kv, d)
    # a padded query's row is garbage in both forms: compare the real ones
    real = MASK[:, :, None, None]
    with count_call_sites() as sites:
        out, vjp = jax.vjp(lambda q, k, v: real * flash_attention(
            q, k, v, MASK, BLOCK, BLOCK, causal=True, window=window), q, k, v)
    assert sites[path] == 1 == sites["lane_indexed"] + sites["transposed"]
    # a call under a window says so, with its tiles (T 80 is five blocks)
    assert sites["window"] == 1 and sites["window_tiles_causal"] == 15
    assert sites["window_tiles_live"] == live_tiles(T, BLOCK, BLOCK, window)
    want, want_vjp = jax.vjp(
        lambda q, k, v: real * _dense(q, k, v, MASK, window), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for name, g, w in zip("qkv", vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16)])
def test_unequal_blocks_and_a_padded_sequence(blocks):
    """T = 80 is padded to 96 for blocks of 32; the window's band crosses
    tiles that are not square."""
    q, k, v, cot = _operands(4, 2, 128)
    real = MASK[:, :, None, None]
    out, vjp = jax.vjp(lambda q, k, v: real * flash_attention(
        q, k, v, MASK, *blocks, causal=True, window=20), q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: real * _dense(q, k, v, MASK, 20), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


@pytest.mark.parametrize("which", sorted(LAYOUTS))
def test_a_window_as_long_as_the_sequence_is_causal(which):
    h, kv, d, _ = LAYOUTS[which]
    q, k, v, cot = _operands(h, kv, d)
    run = lambda **kw: jax.vjp(lambda q, k, v: flash_attention_lse(  # noqa: E731
        q, k, v, MASK, BLOCK, BLOCK, causal=True, **kw), q, k, v)
    (out, lse), vjp = run(window=T)
    (want, want_lse), want_vjp = run()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(want_lse))
    zero = jnp.zeros_like(lse)
    for g, w in zip(vjp((cot, zero)), want_vjp((cot, zero))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_under_vmap_checkpoint_and_grad_in_bfloat16():
    """As a model's client step holds it: a client axis over the call, the
    layer rematerialised, bfloat16 operands, the gradient of a scalar."""
    c = 3
    q, k, v, _ = (jnp.stack([x * (1 + 0.1 * i) for i in range(c)])
                  for x in _operands(4, 2, 128, jnp.bfloat16))
    masks = jnp.stack([MASK, MASK[::-1], MASK])

    def loss(attend):
        def one(q, k, v, mask):
            out = jax.checkpoint(lambda q, k, v: attend(q, k, v, mask))(
                q, k, v)
            return jnp.sum(jnp.square(out.astype(jnp.float32)
                                      * mask[:, :, None, None]))
        return lambda q, k, v: jnp.sum(jax.vmap(one)(q, k, v, masks))

    got = jax.grad(loss(lambda q, k, v, m: flash_attention(
        q, k, v, m, BLOCK, BLOCK, causal=True, window=24)), (0, 1, 2))(q, k, v)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = jax.grad(loss(lambda q, k, v, m: _dense(q, k, v, m, 24)),
                    (0, 1, 2))(*f32)
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == jnp.bfloat16
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   atol=0.03 * scale, err_msg=name)


def test_the_live_tile_count():
    """The cell's shapes: 8,192 positions, a window of 2,048, blocks of
    512: 70 of the causal 136 tiles a head. And the edges: a window of one
    block reaches one tile back (its first query sees the block before it);
    a window of 1 keeps the diagonal tiles alone."""
    assert live_tiles(8192, 512, 512) == 136
    assert live_tiles(8192, 512, 512, 2048) == 70
    assert live_tiles(8192, 512, 512, 8192) == 136
    assert live_tiles(8192, 512, 512, 1) == 16
    assert live_tiles(8192, 512, 512, 512) == 31
    assert live_tiles(8192, 512, 512, 513) == 31
    assert live_tiles(8192, 512, 512, 514) == 16 + 15 + 14
    assert live_tiles(8192, 512, 512, 1025) == 16 + 15 + 14
    assert live_tiles(96, 16, 32, 20) == sum(
        1 for i in range(0, 96, 16) for j in range(0, 96, 32)
        if any(0 <= a - b < 20 for a in range(i, i + 16)
               for b in range(j, j + 32)))


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)])
def test_live_tiles_are_the_tiles_that_hold_a_visible_score(blocks):
    bq, bk = blocks
    for window in (1, 15, 16, 17, 40, 96):
        holds = sum(
            1 for i in range(0, 96, bq) for j in range(0, 96, bk)
            if any(0 <= a - b < window for a in range(i, i + bq)
                   for b in range(j, j + bk)))
        assert live_tiles(96, bq, bk, window) == holds, (blocks, window)


def test_tiles_behind_the_window_are_not_executed():
    """A NaN in v at the first key block: a tile that is executed with all
    of its scores masked still multiplies its zero probabilities by the
    block of v (0 * NaN), so every query block that ran the tile reads NaN.
    Under a window of one block the query blocks from the third on never
    run it, in any of the three kernels (dQ of those rows, and dK / dV of
    the LAST key block, whose live query blocks are its own and none
    earlier, are finite; ``causal`` alone runs the tile and reads NaN)."""
    q, k, v, cot = _operands(4, 2, 128)
    ones = jnp.ones((B, T))
    v = v.at[:, :BLOCK].set(jnp.nan)

    def run(window):
        out, vjp = jax.vjp(lambda q, k: flash_attention(
            q, k, v, ones, BLOCK, BLOCK, causal=True, window=window), q, k)
        return (out, *vjp(cot))

    out, dq, _ = run(BLOCK)
    assert np.isfinite(np.asarray(out[:, 2 * BLOCK:])).all()
    assert np.isfinite(np.asarray(dq[:, 2 * BLOCK:])).all()
    assert np.isnan(np.asarray(out[:, :BLOCK])).any()
    out, dq, _ = run(None)
    assert np.isnan(np.asarray(out[:, 2 * BLOCK:])).any()
    # dK / dV: a NaN in dO at the LAST query block reaches a key block only
    # through a tile that is executed
    q, k, v, cot = _operands(4, 2, 128)
    cot = cot.at[:, -BLOCK:].set(jnp.nan)
    for window, finite in ((BLOCK, True), (None, False)):
        _, vjp = jax.vjp(lambda k, v: flash_attention(
            q, k, v, ones, BLOCK, BLOCK, causal=True, window=window), k, v)
        dk, dv = vjp(cot)
        for g in (dk, dv):
            assert np.isfinite(np.asarray(g[:, :BLOCK])).all() == finite


def test_a_window_needs_causal_and_a_length():
    q, k, v, _ = _operands(4, 2, 128)
    with pytest.raises(ValueError, match="needs causal=True"):
        flash_attention(q, k, v, MASK, BLOCK, BLOCK, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, MASK, BLOCK, BLOCK, causal=True, window=0)


# sha256 of str(jax.make_jaxpr(...)) of the gradient of the four call shapes
# the accepted cells hold (at toy lengths), taken on the parent of the PR
# that brought ``window`` (4225318, jax 0.9.0): without a window the calls
# trace to what they traced to. A PR that changes the kernels on purpose
# takes the values anew and says so.
WITHOUT_A_WINDOW = {
    "encoder: 4 packed heads of 64, not causal":
        "0e10019bc5652cf64a6b31551366c7ae505aed6557327aa9e086276631d51eea",
    "jamba: 3 heads of 128 over one shared head, causal":
        "9bb2e522c56ec15c1096a35037253d899048ca9e6ac17e2b2fb94d2bc1c3d9d3",
    "deepseek: parts 128 + 64 over a shared rotary key, v 128, causal, scale":
        "7afaaef059922f60dafc0050509d6e726d2f55a3195ad4d8a902621f5aa3ef88",
    "nemotron: 4 heads over 2 grouped heads of 128, causal":
        "ca1caee0a5586ec2fe2d3e8f3d5e32c25a7c502c92d4f2411aa2ee020a645301",
}


def _call_shape(which):
    one = lambda *s: jnp.ones(s, jnp.bfloat16)  # noqa: E731
    if which.startswith("encoder"):
        return (one(1, 48, 4, 64),) * 3, {}
    if which.startswith("jamba"):
        return (one(1, 48, 3, 128), one(1, 48, 1, 128),
                one(1, 48, 1, 128)), {"causal": True}
    if which.startswith("deepseek"):
        return ((one(1, 48, 2, 128), one(1, 48, 2, 64)),
                (one(1, 48, 2, 128), one(1, 48, 1, 64)),
                one(1, 48, 2, 128)), {"causal": True, "scale": 0.1}
    return (one(1, 48, 4, 128), one(1, 48, 2, 128),
            one(1, 48, 2, 128)), {"causal": True}


@pytest.mark.parametrize("which", sorted(WITHOUT_A_WINDOW))
def test_without_a_window_the_calls_trace_to_the_parents_program(which):
    operands, kwargs = _call_shape(which)
    mask = jnp.ones((1, 48))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask, 16, 16, **kwargs
                                       ).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*operands))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        WITHOUT_A_WINDOW[which]
