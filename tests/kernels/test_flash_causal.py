"""Causal flash attention (a static ``causal`` argument of the three
kernels) against dense attention: one key/value head shared by every query
head, head size 128, padded tails, block pairs that do and do not divide,
forward and gradients, under ``vmap``; the walk of the live range in whole
trips (PR 44) at sixteen blocks of four to a trip. And ``causal=False``
traces the kernels it always traced."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (flash_attention,
                                                   flash_attention_lse)

# the package exports a function of the module's name over it
flash_module = importlib.import_module(
    "fl4health_tpu.kernels.flash_attention")

B, T, H, D = 2, 80, 3, 128


def _dense(q, k, v, mask, causal):
    t, d = q.shape[1], q.shape[-1]
    k, v = jnp.broadcast_to(k, q.shape), jnp.broadcast_to(v, q.shape)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    keep = mask[:, None, None, :] > 0
    if causal:
        keep = keep & (jnp.arange(t)[None, :]
                       <= jnp.arange(t)[:, None])[None, None]
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)


def _operands(kv_heads, lead=()):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (*lead, B, T, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (*lead, B, T, kv_heads, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (*lead, B, T, kv_heads, D))
    lengths = jnp.asarray([[T, 50], [33, 64]])
    lengths = lengths if lead else lengths[0]
    mask = (jnp.arange(T) < lengths[..., None]).astype(jnp.float32)
    return q, k, v, mask


@pytest.mark.parametrize("bq,bk", [(32, 32), (32, 16), (16, 32), (48, 32)])
@pytest.mark.parametrize("kv_heads", [1, H])
def test_causal_forward_and_gradients_match_dense(bq, bk, kv_heads):
    q, k, v, mask = _operands(kv_heads)
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape) * mask[
        ..., None, None]

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, bq, bk, causal=True)

    def dense(q, k, v):
        return _dense(q, k, v, mask, True)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v) * mask[..., None, None]),
        np.asarray(dense(q, k, v) * mask[..., None, None]), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape  # the shared head's gradient is summed
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
@pytest.mark.parametrize("kv_heads", [1, H])
def test_the_causal_walk_in_whole_trips_matches_dense(monkeypatch, bq, bk,
                                                      kv_heads):
    """T 256 at four blocks of 16 to a trip, as T 8,192 at blocks of 512 on
    the chip: a query block's interior key blocks go as the binary digits of
    what is short of a trip, then whole straight-line trips under a trip
    count of the grid index, then the diagonal's tile; in dK/dV the
    diagonal's tile first. Against dense attention, forward and gradients,
    a padded tail on the second sequence."""
    monkeypatch.setattr(flash_module, "_TILES_PER_TRIP", 4)
    t = 256
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, t, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, t, kv_heads, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, t, kv_heads, D))
    mask = (jnp.arange(t) < jnp.asarray([t, 170])[:, None]).astype(
        jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape) * mask[
        ..., None, None]

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, bq, bk, causal=True)

    def dense(q, k, v):
        return _dense(q, k, v, mask, True)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v) * mask[..., None, None]),
        np.asarray(dense(q, k, v) * mask[..., None, None]), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_one_shared_head_under_the_clients_vmap(causal):
    q, k, v, mask = _operands(1, lead=(2,))
    weight = jax.random.normal(jax.random.PRNGKey(4), q.shape) * mask[
        ..., None, None]

    def total(fn):
        return lambda q, k, v: jnp.sum(jax.vmap(fn)(q, k, v, mask) * weight)

    flash = lambda q, k, v, m: flash_attention(  # noqa: E731
        q, k, v, m, 32, 32, causal=causal)
    dense = lambda q, k, v, m: _dense(q, k, v, m, causal)  # noqa: E731
    got = jax.grad(total(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(total(dense), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


def test_lse_is_the_causal_logsumexp():
    q, k, v, mask = _operands(H)
    _, lse = flash_attention_lse(q, k, v, mask, 32, 32, causal=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    keep = (mask[:, None, None, :] > 0) & (
        jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None]
    want = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("program", ["forward", "backward"])
def test_causal_false_traces_the_kernels_it_always_traced(program):
    """``causal=False`` is the default's jaxpr to the letter, with no
    conditional and no position iota in it; ``causal=True`` has both."""
    q, k, v, mask = _operands(H)

    def trace(**kw):
        def fn(q, k, v):
            return jnp.sum(flash_attention(q, k, v, mask, 32, 32, **kw))
        if program == "backward":
            fn = jax.grad(fn, (0, 1, 2))
        return str(jax.make_jaxpr(fn)(q, k, v))

    default, off, on = trace(), trace(causal=False), trace(causal=True)
    assert off == default
    assert " cond[" not in off and "iota[" not in off
    assert " cond[" in on and "iota[" in on
