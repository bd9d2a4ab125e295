"""Narrow heads packed side by side in a 128-lane block: heads of 64 (32)
lanes ride two (four) to a lane block of ``[B, T, H*w]``, a grid step holds
the group and runs the kernel's body once a head over the same tiles, told
apart by a lane mask. Forward, dQ / dK / dV and the ``lse`` cotangent against
dense attention and against the transposed path (the same dots, softmax
state and rounding points), in interpret mode; and which shapes the rule
leaves to the transposed path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (_lane_kinds, _transposed,
                                                   count_call_sites,
                                                   flash_attention,
                                                   flash_attention_lse)

B, T = 2, 40  # T is no multiple of the blocks below: a padded sequence tail
BLOCK_Q, BLOCK_K = 16, 8
PACKED = [(12, 64), (4, 32), (2, 64)]  # (heads, width)


def _operands(h, d, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(h * d), 4)
    return tuple(jax.random.normal(k, (B, T, h, d)).astype(dtype)
                 for k in keys)


def _dense_lse(q, k, v, mask, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    keep = mask[:, None, None, :] > 0
    if causal:
        keep = keep & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    s = jnp.where(keep, s, -1e30)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def _objective(attend, cot):
    """A scalar that reads both results, so ``lse`` has a cotangent."""
    def f(q, k, v):
        out, lse = attend(q, k, v)
        return (jnp.sum(out.astype(jnp.float32) * cot)
                + jnp.sum(jnp.sin(lse)))
    return f


# a padded tail at the default scale; no mask with a scale given; both
VARIANTS = {"padded": (True, None), "scaled": (False, 0.1),
            "padded_and_scaled": (True, 0.07)}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,d", PACKED)
def test_packed_heads_match_dense_and_the_transposed_path(h, d, causal,
                                                          variant):
    padded, scale = VARIANTS[variant]
    q, k, v, cot = _operands(h, d)
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0) if padded else None
    ones = jnp.ones((B, T)) if mask is None else mask
    want_scale = scale or d ** -0.5

    def flash(q, k, v):
        return flash_attention_lse(q, k, v, mask, BLOCK_Q, BLOCK_K,
                                   causal=causal, scale=scale)

    def transposed(q, k, v):
        return _transposed(q, k, v, mask, BLOCK_Q, BLOCK_K, True, causal,
                           want_scale)

    def dense(q, k, v):
        return _dense_lse(q, k, v, ones, causal, want_scale)

    with count_call_sites() as sites:
        out, lse = flash(q, k, v)
    assert (sites["lane_indexed"], sites["transposed"]) == (1, 0)
    with count_call_sites() as sites:
        t_out, t_lse = transposed(q, k, v)
    # called directly
    assert (sites["lane_indexed"], sites["transposed"]) == (0, 0)
    d_out, d_lse = dense(q, k, v)
    assert out.shape == (B, T, h, d) and lse.shape == (B, h, T)
    # a query row of a padded batch entry past its last key still has keys
    # (the mask is over keys), so every row compares
    np.testing.assert_allclose(np.asarray(out), np.asarray(d_out), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(d_lse), atol=2e-5,
                               rtol=2e-5)
    # the transposed path runs the same dots over the same blocks: zeros
    # added to a float32 sum are all that differs
    np.testing.assert_allclose(np.asarray(out), np.asarray(t_out), atol=2e-6,
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(t_lse), atol=2e-6,
                               rtol=2e-6)
    grads = [jax.grad(_objective(f, cot), argnums=(0, 1, 2))(q, k, v)
             for f in (flash, dense, transposed)]
    for name, g, w, t in zip(("dq", "dk", "dv"), *grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(g), np.asarray(t), atol=5e-6,
                                   rtol=5e-6, err_msg=name)


@pytest.mark.parametrize("h,d", PACKED)
def test_packed_bfloat16_operands_round_where_the_transposed_path_does(h, d):
    q, k, v, cot = _operands(h, d, jnp.bfloat16)
    cot = cot.astype(jnp.float32)
    mask = jnp.ones((B, T)).at[0, 33:].set(0.0)

    def flash(q, k, v):
        return flash_attention_lse(q, k, v, mask, BLOCK_Q, BLOCK_K,
                                   causal=True)

    def transposed(q, k, v):
        return _transposed(q, k, v, mask, BLOCK_Q, BLOCK_K, True, True,
                           d ** -0.5)

    out, lse = flash(q, k, v)
    t_out, t_lse = transposed(q, k, v)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    # one bf16 rounding of out on either path, from float32 values that
    # agree to the last places
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(t_out, np.float32), atol=2 ** -7,
                               rtol=2 ** -7)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(t_lse), atol=1e-5)
    got = jax.grad(_objective(flash, cot), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_objective(transposed, cot), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16 and g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32)))) \
            <= 2 ** -7 * scale


def test_packed_parts_add_their_scores():
    """q and k as two parts of 32 lanes, four heads a lane block each."""
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    qs = tuple(jax.random.normal(kk, (B, T, 4, 32)) for kk in keys[:2])
    ks = tuple(jax.random.normal(kk, (B, T, 4, 32)) for kk in keys[2:4])
    v, cot = (jax.random.normal(kk, (B, T, 4, 32)) for kk in keys[4:])
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0)

    def flash(qs, ks, v):
        return flash_attention_lse(qs, ks, v, mask, BLOCK_Q, BLOCK_K,
                                   causal=True)

    def dense(qs, ks, v):
        return _dense_lse(jnp.concatenate(qs, -1), jnp.concatenate(ks, -1), v,
                          mask, True, 64 ** -0.5)

    with count_call_sites() as sites:
        got = jax.tree_util.tree_leaves(
            jax.grad(_objective(flash, cot), argnums=(0, 1, 2))(qs, ks, v))
    assert (sites["lane_indexed"], sites["transposed"]) == (1, 0)
    want = jax.tree_util.tree_leaves(
        jax.grad(_objective(dense, cot), argnums=(0, 1, 2))(qs, ks, v))
    assert len(got) == 5
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


def test_packed_heads_under_vmap_are_each_clients_own_call():
    q, k, v, cot = _operands(2, 64)
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0)
    stack = lambda a: jnp.stack([a, 0.5 * a])  # noqa: E731

    def flash(q, k, v, m):
        return flash_attention(q, k, v, m, BLOCK_Q, BLOCK_K)

    def dense(q, k, v, m):
        return _dense_lse(q, k, v, m, False, 64 ** -0.5)[0]

    args = (stack(q), stack(k), stack(v), jnp.stack([mask, mask]))
    out, vjp = jax.vjp(jax.vmap(flash), *args)
    ref, ref_vjp = jax.vjp(jax.vmap(dense), *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for g, w in zip(vjp(stack(cot))[:3], ref_vjp(stack(cot))[:3]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


# what the rule leaves to the transposed path: (q heads, k / v heads, q / k
# width, v width)
STAY_TRANSPOSED = {
    "an odd count of 64-lane heads": (3, 3, 64, 64),
    "96 lanes divide no lane block": (4, 4, 96, 96),
    "80 lanes": (2, 2, 80, 80),
    "16 lanes: eight heads a block were never built": (8, 8, 16, 16),
    "one head of 64": (1, 1, 64, 64),
    "q and k of 64 over a v of 32": (4, 4, 64, 32),
    "one shared key/value head of 64": (4, 1, 64, 64),
}


@pytest.mark.parametrize("which", sorted(STAY_TRANSPOSED))
def test_shapes_outside_the_rule_stay_transposed(which):
    h, kv_heads, d, dv = STAY_TRANSPOSED[which]
    q = jax.ShapeDtypeStruct((B, T, h, d), jnp.float32)
    k = jax.ShapeDtypeStruct((B, T, kv_heads, d), jnp.float32)
    v = jax.ShapeDtypeStruct((B, T, kv_heads, dv), jnp.float32)
    with count_call_sites() as sites:
        out = jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, None, BLOCK_Q, BLOCK_K),
            q, k, v)
    assert out.shape == (B, T, h, dv)
    assert (sites["lane_indexed"], sites["transposed"]) == (0, 1)


def test_fewer_key_heads_than_query_heads_are_not_packed():
    """4 query heads of 64 over 2 key heads: grouped-query attention is not
    the packed kind's (a block's two heads of q would meet ONE head of k),
    so the rule hands it to the transposed path, as before."""
    q = jnp.ones((B, T, 4, 64))
    kv = jnp.ones((B, T, 2, 64))
    assert _lane_kinds((q,), (kv,), kv) is None
    assert _lane_kinds((q,), (q,), q) == (("lane",), ("lane",), "lane", 2, 2,
                                          1)
