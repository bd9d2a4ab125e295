"""``kernels/row_combine.py add_rows``: a scatter-add of rows that are unique
inside each block, as row copies (the interpreter here; the v5e's compiler
takes the call at the window cell's shape in ``test_flash_v5e_compile.py``)
against ``y.at[rows].add``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels import row_combine

N, BLOCK = 96, 8


def _rows(key, blocks, dead_every=0):
    """``blocks`` blocks of BLOCK row indices, unique inside a block, the
    same rows free to come again in the next; every ``dead_every``-th index
    sent past the rows."""
    rows = jnp.concatenate([
        jax.random.permutation(k, N)[:BLOCK]
        for k in jax.random.split(key, blocks)]).astype(jnp.int32)
    if dead_every:
        rows = jnp.where(jnp.arange(rows.shape[0]) % dead_every == 0,
                         N + jnp.arange(rows.shape[0]), rows)
    return rows


@pytest.mark.parametrize("width, dead_every", [(128, 0), (256, 3), (384, 1),
                                               (24, 3)])
def test_add_rows_is_a_scatter_add(width, dead_every):
    slab = row_combine.slab(width)
    assert slab == ((width // 128, 128) if width % 128 == 0 else (width,))
    keys = jax.random.split(jax.random.PRNGKey(width), 3)
    rows = _rows(keys[0], 5, dead_every)
    y = jax.random.normal(keys[1], (N, *slab))
    updates = jax.random.normal(keys[2], (rows.shape[0], *slab))
    got = jax.jit(lambda y, r, u: row_combine.add_rows(y, r, u, BLOCK))(
        y, rows, updates)
    want = y.at[rows].add(updates, mode="drop")
    # a row met in several blocks is summed in the blocks' order on both
    # sides: the same float32 additions
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if dead_every == 1:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(y))
    else:
        assert float(jnp.abs(got - y).max()) > 0.5


def test_the_slab_form_takes_the_call_and_a_narrow_row_does_not():
    def names(width):
        slab = row_combine.slab(width)
        jaxpr = jax.make_jaxpr(
            lambda y, r, u: row_combine.add_rows(y, r, u, BLOCK))(
                jnp.zeros((N, *slab)), jnp.zeros((2 * BLOCK,), jnp.int32),
                jnp.zeros((2 * BLOCK, *slab)))
        return str(jaxpr)

    assert "pallas_call" in names(256) and "scatter-add" not in names(256)
    assert "pallas_call" not in names(24) and "scatter-add" in names(24)
