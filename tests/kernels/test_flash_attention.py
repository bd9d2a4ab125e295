"""Flash-attention kernel vs the dense reference — forward and gradients
(interpret mode on CPU; the same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.parallel.ring_attention import _dense_attention


def _qkv(key, b, t, h, d):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (jax.random.normal(kq, shape), jax.random.normal(kk, shape),
            jax.random.normal(kv, shape))


def _assert_close(a, b, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol,
                               rtol=1e-4)


@pytest.mark.parametrize("t,d", [(128, 64), (100, 48), (256, 128)])
def test_forward_matches_dense(t, d):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, t, 2, d)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = _dense_attention(q, k, v)
    _assert_close(out, ref)


@pytest.mark.parametrize("bq,bk", [(48, 32), (32, 48)])
def test_forward_non_dividing_block_pair(bq, bk):
    # regression: T must pad to lcm(block_q, block_k) — padding to max()
    # silently dropped trailing key blocks for non-dividing pairs
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 48, 2, 32)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    _assert_close(out, _dense_attention(q, k, v))


def test_forward_with_padding_mask():
    t = 96
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, t, 2, 32)
    lengths = jnp.asarray([t, 40])
    mask = (jnp.arange(t)[None, :] < lengths[:, None]).astype(jnp.float32)
    out = flash_attention(q, k, v, pad_mask=mask, block_q=32, block_k=32)
    ref = _dense_attention(q, k, v, pad_mask=mask)
    # only compare rows attending over real keys; padded-query rows are
    # downstream-masked in both impls but normalized differently
    _assert_close(out[0], ref[0])
    _assert_close(out[1, :40], ref[1, :40])


def test_gradients_match_dense():
    t, d = 64, 32
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, t, 2, d)
    mask = (jnp.arange(t)[None, :] < 50).astype(jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(3), q.shape)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, pad_mask=mask, block_q=32, block_k=32)
        return jnp.sum(jnp.square((out - tgt) * mask[..., None, None]))

    def loss_dense(q, k, v):
        out = _dense_attention(q, k, v, pad_mask=mask)
        return jnp.sum(jnp.square((out - tgt) * mask[..., None, None]))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        _assert_close(a, b, atol=5e-4)


def test_jit_and_vmap_compose():
    # engine usage: jitted loss over a vmapped client axis
    q, k, v = _qkv(jax.random.PRNGKey(4), 3, 32, 1, 16)
    cq = jnp.stack([q, q * 0.5])  # [clients, B, T, H, D]
    ck, cv = jnp.stack([k, k]), jnp.stack([v, v])

    @jax.jit
    @jax.vmap
    def per_client(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16)

    out = per_client(cq, ck, cv)
    assert out.shape == cq.shape
    _assert_close(out[0], _dense_attention(q, k, v))
    _assert_close(out[1], _dense_attention(q * 0.5, k, v))


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr
            elif hasattr(item, "eqns"):
                yield item


def _eqns_named(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr``, nested calls included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in _sub_jaxprs(eqn):
            found.extend(_eqns_named(sub, primitive))
    return found


@pytest.mark.parametrize("heads", [2, 3])
@pytest.mark.parametrize("dtype,precision", [
    (jnp.bfloat16, jax.lax.Precision.DEFAULT),
    (jnp.float32, jax.lax.Precision.HIGHEST),
])
def test_dots_take_the_operands_dtype_and_accumulate_in_float32(dtype,
                                                                precision,
                                                                heads):
    """What proves the native-operand path engages: in the three kernels'
    jaxprs every dot takes its operands in the dtype they arrived in (bf16
    straight to the MXU, f32 at HIGHEST) and accumulates in float32. Two
    heads of 64 are packed in one lane block, so a grid step runs the body
    twice: the same dots a head as three heads on the transposed path."""
    x = jnp.ones((1, 256, heads, 64), dtype)
    mask = jnp.ones((1, 256), jnp.float32)

    def loss(q, k, v):
        out = flash_attention(q, k, v, mask, block_q=128, block_k=128)
        return jnp.sum(out.astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    kernels = {eqn.params["name"]: eqn.params["jaxpr"]
               for eqn in _eqns_named(traced.jaxpr, "pallas_call")}
    assert sorted(kernels) == ["flash_dkv", "flash_dq", "flash_fwd"]
    # two blocks of the streamed axis, unrolled: dots a block and head x 2
    a_step = 2 if heads == 2 else 1
    n_dots = {"flash_fwd": 2 * 2 * a_step, "flash_dq": 3 * 2 * a_step,
              "flash_dkv": 4 * 2 * a_step}
    for name, kernel in kernels.items():
        dots = _eqns_named(kernel, "dot_general")
        assert len(dots) == n_dots[name], (name, len(dots))
        for dot in dots:
            assert [v.aval.dtype for v in dot.invars] == [dtype, dtype], name
            assert dot.params["preferred_element_type"] == jnp.float32, name
            assert set(dot.params["precision"]) == {precision}, name
        # nothing of the softmax arithmetic runs below float32
        narrow_math = [e.primitive.name for e in kernel.eqns
                       if e.primitive.name in ("exp", "log", "max", "reduce_max",
                                               "reduce_sum", "sub")
                       and any(v.aval.dtype != jnp.float32 for v in e.outvars)]
        assert not narrow_math, (name, narrow_math)


_BF16_EPS = float(jnp.finfo(jnp.bfloat16).eps)  # 2**-7


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
def test_bf16_forward_and_gradients_match_float32_dense(bq, bk, d, masked):
    """bf16 operands, float32 softmax and accumulators, against dense
    attention computed in float32 from the same bf16 inputs. Tolerances from
    bf16's epsilon (2**-7, half of it a rounding): the output is rounded once
    to bf16 and p once before its dot, so |out - ref| <= eps * max|ref|; a
    gradient adds the roundings of dO's product with p and of dS before its
    dot to its own, so 2 * eps * max|ref|. A wrong mask or block offset moves
    these by tenths."""
    t = 256
    keys = jax.random.split(jax.random.PRNGKey(d + bq), 4)
    q, k, v, tgt = (jax.random.normal(kk, (1, t, 2, d)).astype(jnp.bfloat16)
                    for kk in keys)
    mask = ((jnp.arange(t)[None, :] < 150).astype(jnp.float32) if masked
            else None)
    w = (mask if masked else jnp.ones((1, t)))[..., None, None]

    def loss(attend):
        def f(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(jnp.square((out - tgt.astype(jnp.float32)) * w)), out
        return f

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, block_q=bq, block_k=bk)

    def dense(q, k, v):
        return _dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                pad_mask=mask)

    (_, out_f), g_f = jax.value_and_grad(loss(flash), argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, out_d), g_d = jax.value_and_grad(loss(dense), argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    assert out_f.dtype == jnp.float32 and g_f[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out_f * w), np.asarray(out_d * w), rtol=0,
        atol=_BF16_EPS * float(jnp.max(jnp.abs(out_d * w))))
    for got, ref in zip(g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)), np.asarray(ref), rtol=0,
            atol=2 * _BF16_EPS * float(jnp.max(jnp.abs(ref))))


def test_long_axis_loops_over_bounded_trips():
    """More key blocks than one trip unrolls (and a remainder that does not
    fill a trip): the kernels loop over trips and still match dense."""
    from fl4health_tpu.kernels.flash_attention import _TILES_PER_TRIP

    t, blk = 8 * (_TILES_PER_TRIP * 2 + 3), 8
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, t, 1, 16)
    mask = (jnp.arange(t)[None, :] < t - 20).astype(jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.square(
            attend(q, k, v) * mask[..., None, None]))

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, block_q=blk, block_k=blk)

    def dense(q, k, v):
        return _dense_attention(q, k, v, pad_mask=mask)

    traced = jax.make_jaxpr(flash)(q, k, v)
    assert _eqns_named(_eqns_named(traced.jaxpr, "pallas_call")[0]
                       .params["jaxpr"], "scan")
    _assert_close(flash(q, k, v)[0, :t - 20], dense(q, k, v)[0, :t - 20])
    for got, ref in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                        jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)):
        _assert_close(got, ref, atol=5e-4)


@pytest.mark.slow
def test_transformer_with_flash_attention_matches_dense():
    # the kernel as the transformer's attention core (models/transformer.py
    # attention_fn seam — same plug point ring attention uses)
    import functools

    from fl4health_tpu.models.transformer import TransformerClassifier

    kwargs = dict(vocab_size=64, n_classes=3, d_model=32, n_heads=2,
                  n_layers=2, d_ff=64, max_len=32)
    dense_m = TransformerClassifier(**kwargs)
    flash_m = TransformerClassifier(
        **kwargs,
        attention_fn=functools.partial(flash_attention, block_q=16, block_k=16),
    )
    x = jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0, 64)
    variables = dense_m.init(jax.random.PRNGKey(0), x, train=False)
    (dense_out, _), (flash_out, _) = (
        dense_m.apply(variables, x, train=False),
        flash_m.apply(variables, x, train=False),
    )
    _assert_close(dense_out["prediction"], flash_out["prediction"], atol=1e-4)

    from jax.flatten_util import ravel_pytree

    gd = jax.grad(lambda p: jnp.sum(jnp.square(
        dense_m.apply(p, x, train=False)[0]["prediction"])))(variables)
    gf = jax.grad(lambda p: jnp.sum(jnp.square(
        flash_m.apply(p, x, train=False)[0]["prediction"])))(variables)
    fa = ravel_pytree(gd)[0]
    fb = ravel_pytree(gf)[0]
    np.testing.assert_allclose(np.asarray(fa), np.asarray(fb), atol=2e-3,
                               rtol=1e-3)


class TestCompiledModeBoundary:
    """What Mosaic cannot compile is refused in Python, with the reason
    (checked with interpret=False under eval_shape: nothing is compiled, so
    this runs on the CPU). Interpret mode keeps taking any block size."""

    @staticmethod
    def _shape_only(t, d, dtype, block):
        x = jnp.zeros((1, t, 1, d), dtype)
        return jax.eval_shape(
            lambda x: flash_attention(x, x, x, block_q=block, block_k=block,
                                      interpret=False), x)

    def test_block_64_is_rejected_naming_the_lane_width(self):
        with pytest.raises(ValueError, match="multiple of 128"):
            self._shape_only(512, 64, jnp.bfloat16, 64)

    def test_whole_sequence_block_and_multiples_of_128_pass(self):
        # eval_shape reaches pallas_call's own abstract evaluation: the
        # request got past the Python boundary
        assert self._shape_only(16, 8, jnp.float32, 16).shape == (1, 16, 1, 8)
        assert self._shape_only(512, 64, jnp.bfloat16, 256).shape == (
            1, 512, 1, 64)

    def test_sequence_beyond_the_vmem_limit_is_rejected(self):
        with pytest.raises(ValueError, match="ring_flash_attention"):
            self._shape_only(16384, 128, jnp.float32, 128)
        # the largest pairs that compiled on the chip stay accepted
        self._shape_only(8192, 128, jnp.float32, 128)
        self._shape_only(16384, 64, jnp.bfloat16, 128)


class TestInterpretDefault:
    @pytest.mark.parametrize("backend,expected", [("cpu", True),
                                                  ("tpu", False)])
    def test_cpu_interprets_tpu_compiles(self, monkeypatch, backend, expected):
        from fl4health_tpu.kernels._platform import interpret_default

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert interpret_default() is expected

    def test_any_other_backend_raises(self, monkeypatch):
        from fl4health_tpu.kernels._platform import interpret_default

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            interpret_default()


def test_under_a_traced_mesh_the_kernel_call_goes_manual(eight_devices):
    """XLA cannot auto-partition a Mosaic custom call, so inside a program
    traced for a mesh (RoundProgramBuilder.jit) the kernel wraps itself in a
    shard_map, and the engine's vmap over clients (spmd_axis_name) makes the
    batched axis the "clients" mesh axis — same numbers, one client slice
    per device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(eight_devices[:4]), ("clients",))
    q, k, v = (jnp.stack([x, 0.5 * x, 2.0 * x, -x])
               for x in _qkv(jax.random.PRNGKey(7), 1, 32, 2, 16))

    def per_client(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16)

    def cohort(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.vmap(per_client, spmd_axis_name="clients")(q, k, v)

    sharded = NamedSharding(mesh, P("clients"))
    fn = jax.jit(cohort, in_shardings=sharded, out_shardings=sharded)
    text = fn.lower(q, k, v).as_text()
    assert "manual_computation" in text and '{"clients"}' in text
    out = fn(q, k, v)
    assert len(out.sharding.device_set) == 4
    _assert_close(out, jax.vmap(per_client)(q, k, v))
