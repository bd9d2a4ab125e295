"""Grouped-query attention through the flash calls: fewer key/value heads
than query heads, each serving ``rep`` consecutive query heads, addressed
where the model holds them (``[B, T, Hkv * 128]``, query head h at lane block
``h // rep``) with dK / dV summed over a group's heads in float32 inside the
dK/dV call. Forward and the three gradients against dense attention over the
repeated heads, in interpret mode, for groups of 1, 4, 16 and all heads;
which path a call takes, by its shapes alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (_lane_kinds,
                                                   count_call_sites,
                                                   flash_attention)

B, T, D = 2, 40, 128
# name -> (query heads, key/value heads, the kind of k and v)
GROUPS = {
    "groups of 1 (heads of their own)": (4, 4, "lane"),
    "groups of 4": (8, 2, "grouped"),
    "groups of 16": (32, 2, "grouped"),
    "all heads (one shared head)": (6, 1, "row"),
}


def _operands(h, kv, d=D, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + h), 4)
    draw = lambda key, n: jax.random.normal(key, (B, T, n, d)).astype(dtype)  # noqa: E731
    return (draw(keys[0], h), draw(keys[1], kv), draw(keys[2], kv),
            draw(keys[3], h))


def _dense(q, k, v, mask, causal):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    keep = mask[:, None, None, :] > 0
    if causal:
        keep = keep & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


MASK = (jnp.arange(T)[None, :] < jnp.asarray([[T], [29]])).astype(jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("which", sorted(GROUPS))
def test_grouped_heads_match_dense_attention(which, causal):
    h, kv, kind = GROUPS[which]
    q, k, v, cot = _operands(h, kv)
    assert _lane_kinds((q,), (k,), v) == (
        ("lane",), (kind,), kind, h, 1, h // kv if kind == "grouped" else 1)
    with count_call_sites() as sites:
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, MASK, 16, 8, causal=causal), q, k, v)
    assert (sites["lane_indexed"], sites["transposed"]) == (1, 0)
    want, want_vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, MASK, causal),
                             q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for name, g, w in zip("qkv", vjp(cot), want_vjp(cot)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


def test_a_groups_key_gradient_is_summed_in_float32():
    """bfloat16 operands: dK / dV of a key/value head leave the call as ONE
    float32 sum over its 16 query heads and are rounded once, so they lie
    within one bfloat16 rounding of the float32 reference; sixteen
    gradients rounded each and then added would not."""
    q, k, v, cot = _operands(32, 2, dtype=jnp.bfloat16)
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, MASK, 16, 8, causal=True), q, k, v)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    _, want_vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, MASK, True), *f32)
    for g, w in zip(vjp(cot)[1:], want_vjp(cot.astype(jnp.float32))[1:]):
        assert g.dtype == jnp.bfloat16
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) < 0.03 * scale


def test_a_wrong_group_is_not_the_same_attention():
    """Key head ``h % 2`` for ``h // 16``: what the addressing must not be."""
    q, k, v, _ = _operands(32, 2)
    out = flash_attention(q, k, v, MASK, 16, 8, causal=True)
    interleaved = jnp.stack([k[:, :, i % 2] for i in range(32)], axis=2)
    wrong = _dense(q, interleaved, jnp.stack(
        [v[:, :, i % 2] for i in range(32)], axis=2), MASK, True)
    assert float(jnp.abs(out - wrong).max()) > 0.1


def test_grouped_heads_under_vmap_are_each_clients_own_call():
    q, k, v, cot = _operands(8, 2)
    stack = lambda a: jnp.stack([a, a[::-1]])  # noqa: E731
    attend = lambda q, k, v: flash_attention(q, k, v, MASK, 16, 8,  # noqa: E731
                                             causal=True)
    out, vjp = jax.vjp(jax.vmap(attend), stack(q), stack(k), stack(v))
    want, want_vjp = jax.vjp(jax.vmap(
        lambda q, k, v: _dense(q, k, v, MASK, True)), stack(q), stack(k),
        stack(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(vjp(stack(cot)), want_vjp(stack(cot))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


@pytest.mark.parametrize("h,kv,d", [(4, 2, 64), (8, 2, 24), (6, 4, 128)])
def test_grouped_heads_outside_the_rule_are_repeated_and_transposed(h, kv, d):
    """Narrow grouped heads, or key/value heads that do not divide the query
    heads: the rule leaves them out; narrow ones are repeated to the query
    heads first (the transposed path copies anyway) and still match dense
    attention; a count that does not divide is refused by shape."""
    q, k, v, cot = _operands(h, kv, d)
    assert _lane_kinds((q,), (k,), v) is None
    if h % kv:
        with pytest.raises(Exception):
            flash_attention(q, k, v, MASK, 16, 8)
        return
    with count_call_sites() as sites:
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, MASK, 16, 8, causal=True), q, k, v)
    assert (sites["lane_indexed"], sites["transposed"]) == (0, 1)
    want, want_vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, MASK, True),
                             q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)
