"""The flash kernels with a value head width of their own and a static
``scale`` (latent attention: q and k 192 wide, v 128): forward and all three
gradients against dense attention, causal and not, in interpret mode; and
with equal widths and no ``scale`` the traced program is what it was before
the second width existed."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (_check_compilable,
                                                   flash_attention,
                                                   flash_attention_lse)


def _dense(q, k, v, mask, causal, scale):
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    keep = mask[:, None, None, :] > 0
    if causal:
        keep = keep & (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _operands(d, dv, t=40, b=2, h=2):
    ks = jax.random.split(jax.random.PRNGKey(d + dv), 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    mask = jnp.ones((b, t)).at[1, 29:].set(0.0)
    return q, k, v, mask, jax.random.normal(ks[3], (b, t, h, dv))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,dv,scale", [(192, 128, None), (192, 128, 0.1147),
                                        (24, 8, None), (16, 40, 0.3),
                                        (64, 64, 0.2)])
def test_forward_and_gradients_at_two_widths(d, dv, scale, causal):
    q, k, v, mask, cot = _operands(d, dv)
    want_scale = d ** -0.5 if scale is None else scale

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, 16, 8, causal=causal,
                               scale=scale)

    def dense(q, k, v):
        return _dense(q, k, v, mask, causal, want_scale)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(dense, q, k, v)
    assert out.shape == (2, 40, 2, dv)
    # rows of the padded tail attend to nothing real in the non-causal case
    # too: compare where a query has a key
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    got, want = vjp(cot), ref_vjp(cot)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


def test_a_shared_head_at_two_widths_sums_its_gradient_over_the_heads():
    q, _, _, mask, cot = _operands(48, 16, h=3)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], (2, 40, 1, 48))
    v = jax.random.normal(ks[1], (2, 40, 1, 16))

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, 8, 8, causal=True)

    def dense(q, k, v):
        return _dense(q, jnp.repeat(k, 3, 2), jnp.repeat(v, 3, 2), mask, True,
                      48 ** -0.5)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for g, w in zip(vjp(cot), ref_vjp(cot)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


def test_lse_is_the_scaled_scores_logsumexp():
    q, k, v, mask, _ = _operands(24, 8)
    _, lse = flash_attention_lse(q, k, v, mask, 8, 8, scale=0.25)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.25
    want = jax.nn.logsumexp(jnp.where(mask[:, None, None, :] > 0, s, -1e30),
                            axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5)


# sha256 of str(jax.make_jaxpr(...)) of the two programs below (jax 0.9.0):
# the accepted cells' transposed flash calls are these calls at other sizes.
# A PR that changes the kernels on purpose takes the values anew and says so.
# Taken on the commit before the second width (3c40b88: c57913b1... /
# 2f8edc45...), and anew at PR 38, whose forward rule names ``out`` and
# ``lse`` for the remat policies and keeps ``lse`` without its trailing axis
# of 1: against 63cb4cf the two jaxprs differ by the two ``name`` equations
# and by reshapes of ``lse`` ([BH, Tp, 1] -> [BH, Tp] once; from there to
# [BH, Tp, 1] for the result and for dQ, and to [BH, 1, Tp] for dK/dV where
# it was reshaped from [BH, Tp, 1]) and by nothing else
# (tests/kernels/test_flash_remat_names.py holds the names to lowering to
# nothing). PR 40 packed heads of 64 two to a lane block, so the second
# program moved from two heads of 64 to three, which stay transposed: its
# value was taken on PR 39's tree (c9d3218) at the new shape and holds on
# this one, as the first, untouched, did until PR 44 took it anew (was
# 9aaf82a8...): a causal call walks the live range of a query block (a key
# block in dK/dV), the positional mask on edge tiles alone; the second, not
# causal, is PR 39's.
BEFORE_THE_SECOND_WIDTH = {
    "causal, one shared key/value head, bfloat16":
        "c4981b7452e9fa1480fa662077204291b59fcf2d63c65ae44d628e700c6c31f0",
    "not causal, three heads of 64, float32, blocks 32/16":
        "0e12726b1a3de754fc0fcc04c60645d77b57d500585fa7e2c3f19df355b0e655",
}


@pytest.mark.parametrize("which", sorted(BEFORE_THE_SECOND_WIDTH))
def test_equal_widths_and_no_scale_trace_to_the_parents_program(which):
    if which.startswith("causal"):
        q = jnp.ones((2, 40, 3, 24), jnp.bfloat16)
        k = v = jnp.ones((2, 40, 1, 24), jnp.bfloat16)
        mask = jnp.ones((2, 40))

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, mask, 16, 16, causal=True
                                           ).astype(jnp.float32))
    else:
        q = k = v = jnp.ones((1, 64, 3, 64), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, 32, 16
                                           ).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        BEFORE_THE_SECOND_WIDTH[which]


def test_limits_name_the_width_they_are_about():
    _check_compilable(512, 512, 1024, 256, 128, jnp.bfloat16)
    _check_compilable(128, 128, 16384, 128, 128, jnp.bfloat16)
    with pytest.raises(ValueError) as e:
        _check_compilable(128, 128, 16384, 256, 128, jnp.bfloat16)
    msg = str(e.value)
    assert "query/key head width of 256" in msg
    assert "value head width of 128" in msg and "12.0 MiB" in msg
    with pytest.raises(ValueError, match="block_q=64"):
        _check_compilable(64, 128, 1024, 256, 128, jnp.bfloat16)
