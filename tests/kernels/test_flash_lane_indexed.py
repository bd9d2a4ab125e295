"""The flash calls over operands where the model holds them (``[B, T, H*w]``,
the head a lane block; a part one head wide shared by index) and the score
as a sum over parts: forward and every part's gradient against dense
attention over the parts' concatenation, in interpret mode; which of the two
paths a call takes, by its shapes alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (count_call_sites,
                                                   flash_attention,
                                                   flash_attention_lse)

B, T, H = 2, 40, 3

# name -> (widths of the parts, which parts of k have ONE head, v's width,
# v has one head, the path the shapes give)
LAYOUTS = {
    # latent attention: 128 lanes without positions, a narrow rotary part
    # whose key every head shares
    "latent": ((128, 8), (False, True), 128, False, "lane_indexed"),
    "one_part": ((128,), (False,), 128, False, "lane_indexed"),
    "two_lane_parts": ((128, 128), (False, False), 256, False,
                       "lane_indexed"),
    # multi-query attention: one key/value head of 128
    "shared_kv": ((128,), (True,), 128, True, "lane_indexed"),
    # narrow heads of their own: the parts are concatenated and copied
    "narrow": ((24, 8), (False, True), 16, False, "transposed"),
    "narrow_v": ((128,), (False,), 64, False, "transposed"),
}


def _operands(layout, dtype=jnp.float32):
    widths, k_shared, dv, v_shared, _ = LAYOUTS[layout]
    keys = iter(jax.random.split(jax.random.PRNGKey(sum(widths) + dv), 16))

    def draw(heads, width):
        return jax.random.normal(next(keys), (B, T, heads, width)).astype(dtype)

    qs = tuple(draw(H, w) for w in widths)
    ks = tuple(draw(1 if one else H, w) for w, one in zip(widths, k_shared))
    return qs, ks, draw(1 if v_shared else H, dv), draw(H, dv)


def _dense(qs, ks, v, mask, causal, scale):
    q = jnp.concatenate(qs, axis=-1)
    k = jnp.concatenate([jnp.broadcast_to(a, (B, T, H, a.shape[-1]))
                         for a in ks], axis=-1)
    v = jnp.broadcast_to(v, (B, T, H, v.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    keep = mask[:, None, None, :] > 0
    if causal:
        keep = keep & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# every layout causal and not, with and without a scale, under a pad mask;
# without a mask the lane-indexed layouts at the default scale
CASES = [(layout, causal, scale, True) for layout in sorted(LAYOUTS)
         for causal in (False, True) for scale in (None, 0.1)]
CASES += [(layout, causal, None, False) for layout in sorted(LAYOUTS)
          if LAYOUTS[layout][-1] == "lane_indexed" for causal in (False, True)]


@pytest.mark.parametrize("layout,causal,scale,padded", CASES)
def test_parts_match_dense_attention_over_their_concatenation(
        layout, causal, scale, padded):
    qs, ks, v, cot = _operands(layout)
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0) if padded else None
    want_scale = scale or sum(LAYOUTS[layout][0]) ** -0.5
    single = len(qs) == 1

    def flash(qs, ks, v):
        return flash_attention(qs[0] if single else qs,
                               ks[0] if single else ks, v, mask, 16, 8,
                               causal=causal, scale=scale)

    def dense(qs, ks, v):
        return _dense(qs, ks, v, jnp.ones((B, T)) if mask is None else mask,
                      causal, want_scale)

    with count_call_sites() as sites:
        out, vjp = jax.vjp(flash, qs, ks, v)
    other = ({"lane_indexed", "transposed"} - {LAYOUTS[layout][-1]}).pop()
    assert sites[LAYOUTS[layout][-1]] == 1 and sites[other] == 0
    ref, ref_vjp = jax.vjp(dense, qs, ks, v)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # a part with one head gets ONE head's worth of gradient: the sum over
    # the query heads, which is what the broadcast's transpose gives dense
    got, want = jax.tree_util.tree_leaves(vjp(cot)), \
        jax.tree_util.tree_leaves(ref_vjp(cot))
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


def test_bfloat16_parts_and_a_shared_parts_gradient_summed_in_float32():
    qs, ks, v, cot = _operands("latent", jnp.bfloat16)
    mask = jnp.ones((B, T)).at[0, 33:].set(0.0)

    def flash(qs, ks, v):
        return flash_attention(qs, ks, v, mask, 8, 8, causal=True)

    def dense(qs, ks, v):
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                     (qs, ks, v))
        return _dense(*f32, mask, True, 136 ** -0.5)

    out, vjp = jax.vjp(flash, qs, ks, v)
    ref, ref_vjp = jax.vjp(dense, qs, ks, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=0.03, rtol=0.03)
    (dqs, dks, dv), (rqs, rks, rv) = vjp(cot), ref_vjp(
        cot.astype(jnp.float32))
    for g, w in zip((*dqs, *dks, dv), (*rqs, *rks, rv)):
        assert g.dtype == jnp.bfloat16 and g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))) \
            < 0.03 * scale


def test_lse_and_its_cotangent_on_the_lane_indexed_path():
    qs, ks, v, cot = _operands("latent")
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0)

    def flash(qs, ks, v):
        out, lse = flash_attention_lse(qs, ks, v, mask, 8, 16, scale=0.07)
        return jnp.sum(out * cot) + jnp.sum(jnp.sin(lse))

    def dense(qs, ks, v):
        q = jnp.concatenate(qs, axis=-1)
        k = jnp.concatenate([jnp.broadcast_to(a, (B, T, H, a.shape[-1]))
                             for a in ks], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.07
        lse = jax.nn.logsumexp(
            jnp.where(mask[:, None, None, :] > 0, s, -1e30), axis=-1)
        return (jnp.sum(_dense(qs, ks, v, mask, False, 0.07) * cot)
                + jnp.sum(jnp.sin(lse)))

    got = jax.tree_util.tree_leaves(jax.grad(flash, argnums=(0, 1, 2))(
        qs, ks, v))
    want = jax.tree_util.tree_leaves(jax.grad(dense, argnums=(0, 1, 2))(
        qs, ks, v))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


def test_under_vmap_each_client_is_its_own_call():
    qs, ks, v, cot = _operands("shared_kv")
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0)
    stack = lambda a: jnp.stack([a, 0.5 * a])  # noqa: E731

    def flash(q, k, v, m):
        return flash_attention(q, k, v, m, 8, 16, causal=True)

    def dense(q, k, v, m):
        return _dense((q,), (k,), v, m, True, 128 ** -0.5)

    args = (stack(qs[0]), stack(ks[0]), stack(v), jnp.stack([mask, mask]))
    out, vjp = jax.vjp(jax.vmap(flash), *args)
    ref, ref_vjp = jax.vjp(jax.vmap(dense), *args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for g, w in zip(vjp(stack(cot))[:3], ref_vjp(stack(cot))[:3]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


def test_under_a_traced_mesh_the_parts_go_manual_too(eight_devices):
    """The lane-indexed call inside a program traced for a mesh: the tuples
    of parts pass through the kernel's own shard_map, one client a device,
    forward and every part's gradient as without the mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(eight_devices[:2]), ("clients",))
    qs, ks, v, cot = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, 0.5 * a]), _operands("latent"))

    def per_client(qs, ks, v, cot):
        return jnp.sum(flash_attention(qs, ks, v, None, 8, 8, causal=True)
                       * cot)

    grads = jax.vmap(jax.grad(per_client, argnums=(0, 1, 2)))

    def cohort(qs, ks, v, cot):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.vmap(jax.grad(per_client, argnums=(0, 1, 2)),
                            spmd_axis_name="clients")(qs, ks, v, cot)

    sharded = NamedSharding(mesh, P("clients"))
    fn = jax.jit(cohort, in_shardings=sharded, out_shardings=sharded)
    assert "manual_computation" in fn.lower(qs, ks, v, cot).as_text()
    for g, w in zip(jax.tree_util.tree_leaves(fn(qs, ks, v, cot)),
                    jax.tree_util.tree_leaves(grads(qs, ks, v, cot))):
        assert len(g.sharding.device_set) == 2
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5,
                                   rtol=2e-5)


def test_parts_come_in_pairs_of_one_width():
    qs, ks, v, _ = _operands("latent")
    with pytest.raises(ValueError, match="pairs of one width"):
        flash_attention(qs, ks[:1], v)
    with pytest.raises(ValueError, match="pairs of one width"):
        flash_attention(qs, (ks[0], ks[1][..., :4]), v)
