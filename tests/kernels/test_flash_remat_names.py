"""The flash forward rule names what its backward reads (``out``, ``lse``:
``SAVED_NAMES``) for the remat policies of ``core/remat.py``: a policy that
keeps them leaves ONE ``flash_fwd`` in the gradient of a rematerialised
function where a bare ``jax.checkpoint`` leaves two; the kept ``lse`` has no
trailing axis of 1; and where no policy asks for them the names lower to
nothing."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.kernels.flash_attention import (FLASH_LSE, FLASH_OUT,
                                                   SAVED_NAMES,
                                                   flash_attention,
                                                   flash_attention_lse)
from tests.models.remat_probe import eqns, pallas_calls

B, T, H, BLOCK = 2, 40, 3, 16
# head width -> the path the shapes give (kernels/flash_attention.py)
PATHS = {"transposed": 24, "lane_indexed": 128}


def _operands(path, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(PATHS[path]), 3)
    q, k, v = (jax.random.normal(key, (B, T, H, PATHS[path])).astype(dtype)
               for key in keys)
    mask = jnp.ones((B, T)).at[1, 29:].set(0.0)
    return q, k, v, mask


def _losses(mask):
    """name -> a scalar function of (q, k, v): ``out`` alone, and ``out``
    with ``lse`` read downstream as ring-flash's merge reads it."""
    def out_only(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, mask, BLOCK, BLOCK,
                                                  causal=True)))

    def out_and_lse(q, k, v):
        out, lse = flash_attention_lse(q, k, v, mask, BLOCK, BLOCK)
        return jnp.sum(out * jnp.exp(lse - 5.0).transpose(0, 2, 1)[..., None])

    return {"flash_attention": out_only, "flash_attention_lse": out_and_lse}


CASES = [(path, api) for path in PATHS for api in _losses(None)]


@pytest.mark.parametrize("path,api", CASES)
def test_a_policy_that_keeps_the_names_leaves_one_forward(path, api):
    q, k, v, mask = _operands(path)
    loss = _losses(mask)[api]
    grads = {}
    for label, wrap in (
            ("plain", lambda f: f),
            ("bare", jax.checkpoint),
            ("kept", functools.partial(
                jax.checkpoint, policy=remat_names.keep(SAVED_NAMES)))):
        fn = jax.grad(wrap(loss), argnums=(0, 1, 2))
        jaxpr = jax.make_jaxpr(fn)(q, k, v).jaxpr
        grads[label] = fn(q, k, v), pallas_calls(jaxpr, "flash_fwd")
        assert pallas_calls(jaxpr, "flash_dq") == 1
        assert pallas_calls(jaxpr, "flash_dkv") == 1
    assert [grads[label][1] for label in ("plain", "bare", "kept")] == [1, 2,
                                                                        1]
    # the kept out / lse are the recomputed out / lse: the same gradients
    for got, want in zip(grads["kept"][0], grads["bare"][0]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(grads["kept"][0], grads["plain"][0]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_kept_lse_has_no_trailing_axis_of_one(path):
    """``[.., Tp, 1]`` float32 is padded 128-fold in HBM tiles: kept a layer,
    the statistic would cost more than ``out``."""
    q, k, v, mask = _operands(path, jnp.bfloat16)
    loss = _losses(mask)["flash_attention"]
    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
        loss, policy=remat_names.keep(SAVED_NAMES))))(q, k, v).jaxpr
    # what a policy keeps of a name is the array the name was given to
    by_name = {eqn.params["name"]: eqn.outvars[0].aval
               for eqn in eqns(jaxpr) if eqn.primitive.name == "name"}
    tp = 48  # 40 padded to the blocks of 16
    want = {"transposed": {FLASH_OUT: (B * H, tp, 64), FLASH_LSE: (B * H, tp)},
            "lane_indexed": {FLASH_OUT: (B, tp, H * 128),
                             FLASH_LSE: (B, H, tp)}}[path]
    assert {n: tuple(a.shape) for n, a in by_name.items()} == want
    assert by_name[FLASH_OUT].dtype == jnp.bfloat16
    assert by_name[FLASH_LSE].dtype == jnp.float32
    assert by_name[FLASH_LSE].shape[-1] != 1
    with remat_names.count_named() as seen:
        jax.eval_shape(jax.grad(loss), q, k, v)
    assert seen == {FLASH_OUT: 2 * int(np.prod(want[FLASH_OUT])),
                    FLASH_LSE: 4 * int(np.prod(want[FLASH_LSE]))}


@pytest.mark.parametrize("path,api", CASES)
def test_outside_a_policy_the_names_lower_to_nothing(path, api, monkeypatch):
    """Forward and gradient, outside any ``jax.checkpoint``: the lowered
    text is the text with ``named`` patched to the identity (ring attention's
    local block is ``flash_attention_lse`` differentiated so)."""
    q, k, v, mask = _operands(path)
    loss = _losses(mask)[api]

    def texts():
        # the serial numbers MLIR gives the private functions (@_where_64)
        # count every equation lowered, a name's too: compare without them
        return [re.sub(r"@(\w+?)_\d+\b", r"@\1",
                       jax.jit(fn).lower(q, k, v).as_text())
                for fn in (loss, jax.grad(loss, argnums=(0, 1, 2)))]

    with_names = texts()
    # the package exports a function of the module's name over the module
    module = importlib.import_module("fl4health_tpu.kernels.flash_attention")
    monkeypatch.setattr(module, "named", lambda x, name: x)
    assert texts() == with_names
    # and the forward alone never reaches the rule that names them
    monkeypatch.undo()
    assert "name[" not in str(jax.make_jaxpr(loss)(q, k, v))
    assert str(jax.make_jaxpr(jax.grad(loss))(q, k, v)).count("name[") == 2


def test_counters_nest_and_close():
    x = jnp.ones((3, 5), jnp.bfloat16)
    with remat_names.count_named() as outer:
        assert remat_names.named(x, "a") is not None
        with remat_names.count_named() as inner:
            remat_names.named(x[:2], "a")
            remat_names.named(x.astype(jnp.float32), "b")
        remat_names.named(x[:1], "a")
    assert inner == {"a": 20, "b": 60}
    # the largest array of a name
    assert outer == {"a": 30, "b": 60}
    np.testing.assert_array_equal(np.asarray(remat_names.named(x, "c")),
                                  np.asarray(x))
    assert "c" not in outer


def test_saved_gauges_count_only_the_names_a_site_keeps():
    def forward(x):
        y = remat_names.named(jnp.tanh(x), "kept")
        return jnp.sum(remat_names.named(y * 2.0, "not_asked_for"))

    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    assert remat_names.saved_gauges(forward, (x,), ("kept", "absent"), 3) == {
        "remat_saved_names": 1, "remat_saved_bytes_per_layer": 3 * 4 * 8 * 4}
    assert remat_names.saved_gauges(forward, (x,), (), 3) == {
        "remat_saved_names": 0, "remat_saved_bytes_per_layer": 0}
