"""A sliding window in the three flash calls (``window``, static, beside
``causal``): query i sees key j iff ``0 <= i - j < window`` and j is no pad.
Forward and the three gradients against dense masked attention for windows
below, at and above a block and beyond the sequence, on the lane-indexed
grouped path and the transposed one, with pad masks, under ``vmap`` +
``checkpoint`` + ``grad``; a window as long as the sequence is ``causal``;
tiles wholly behind the window are not executed (counted, and shown by a
NaN that no executed tile may touch); the walk of the live range (PR 44: one
straight-line body where most query blocks agree on the runs' lengths, whole
trips and binary digits elsewhere, the positional mask on edge tiles only)
at a miniature of the trinity_mini cell's shapes, over grouped, shared and
two-part operands, with its counts against a count over positions; and
without a ``window`` the encoder's call traces to the parent's program, the
three causal ones to the values PR 44 took anew."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.kernels.flash_attention import (count_call_sites,
                                                   flash_attention,
                                                   flash_attention_lse,
                                                   live_tiles, traversal)

# the package exports a function of the module's name over it
flash_module = importlib.import_module(
    "fl4health_tpu.kernels.flash_attention")

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


# ---------------------------------------------------------------------------
# The walk of the live range (PR 44)
# ---------------------------------------------------------------------------

# name -> (q parts' (heads, width), k parts' (heads, width), v's)
OPERANDS = {
    "grouped: 4 heads over 2 of 128": (((4, 128),), ((2, 128),), (2, 128)),
    "shared: 3 heads over one of 128": (((3, 128),), ((1, 128),), (1, 128)),
    "two parts: 128 + 64 over a shared rotary key": (
        ((2, 128), (2, 64)), ((2, 128), (1, 64)), (2, 128)),
}


def _parts(which, t, dtype=jnp.float32, b=2):
    q_parts, k_parts, v_part = OPERANDS[which]
    keys = iter(jax.random.split(jax.random.PRNGKey(t), 8))
    draw = lambda hw: jax.random.normal(  # noqa: E731
        next(keys), (b, t, *hw)).astype(dtype)
    qs, ks = tuple(map(draw, q_parts)), tuple(map(draw, k_parts))
    return qs, ks, draw(v_part), draw((q_parts[0][0], v_part[1]))


def _dense_parts(qs, ks, v, mask, window):
    h = qs[0].shape[2]
    wide = lambda x: jnp.repeat(x, h // x.shape[2], axis=2)  # noqa: E731
    q = jnp.concatenate(qs, -1)
    k = jnp.concatenate([wide(x) for x in ks], -1)
    return _dense(q, k, wide(v), mask, window)


def _flash_parts(qs, ks, v, mask, bq, bk, window):
    one = lambda x: x[0] if len(x) == 1 else x  # noqa: E731
    return flash_attention(one(qs), one(ks), v, mask, bq, bk, causal=True,
                           window=window)


@pytest.fixture
def trips_of_four(monkeypatch):
    """Blocks of 16 at four to a trip: what blocks of 512 are to the 64
    tiles of 128 x 128 of a trip on the chip, so T 256 under a window of 64
    walks as the cell's T 8,192 does under 2,048 (16 blocks, runs of
    (1, 3, 1) past the first window, whole trips on a full layer)."""
    monkeypatch.setattr(flash_module, "_TILES_PER_TRIP", 4)


# T, block_q, block_k: one block pair that divides, a padded sequence under
# unequal blocks both ways, and the miniature of the cell
SHAPES = [(48, 16, 16), (80, 32, 16), (80, 16, 32), (256, 16, 16)]


@pytest.mark.parametrize("window", [1, 16, 40, 300, None])
@pytest.mark.parametrize("t,bq,bk", SHAPES)
def test_the_range_walk_matches_dense_masked_attention(trips_of_four, t, bq,
                                                       bk, window):
    """window: one position, one block, between blocks, beyond the
    sequence, none; the three kernels on grouped heads."""
    qs, ks, v, cot = _parts("grouped: 4 heads over 2 of 128", t)
    mask = (jnp.arange(t)[None, :] < jnp.asarray([[t], [t - 23]])
            ).astype(jnp.float32)
    real = mask[:, :, None, None]
    out, vjp = jax.vjp(lambda qs, ks, v: real * _flash_parts(
        qs, ks, v, mask, bq, bk, window), qs, ks, v)
    want, want_vjp = jax.vjp(lambda qs, ks, v: real * _dense_parts(
        qs, ks, v, mask, window), qs, ks, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(jax.tree_util.tree_leaves(vjp(cot)),
                    jax.tree_util.tree_leaves(want_vjp(cot))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   rtol=5e-5)


@pytest.mark.parametrize("window", [64, None])
@pytest.mark.parametrize("which", sorted(OPERANDS))
def test_the_cells_miniature_under_vmap_checkpoint_and_grad_in_bfloat16(
        trips_of_four, which, window):
    """T 256 at blocks of 16, four to a trip: a sliding layer's steady
    straight-line body and its first query blocks' digits, a full layer's
    whole trips; every kind of operand the causal cells hold, as a client
    step holds the call."""
    t, c = 256, 2
    qs, ks, v, _ = _parts(which, t, jnp.bfloat16, b=1)
    qs, ks, v = jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (1 + 0.1 * i) for i in range(c)]),
        (qs, ks, v))
    masks = (jnp.arange(t)[None, None, :]
             < jnp.asarray([t, 201])[:, None, None]).astype(jnp.float32)

    def loss(attend):
        def one(qs, ks, v, mask):
            out = jax.checkpoint(lambda qs, ks, v: attend(qs, ks, v, mask))(
                qs, ks, v)
            return jnp.sum(jnp.square(out.astype(jnp.float32)
                                      * mask[:, :, None, None]))
        return lambda qs, ks, v: jnp.sum(jax.vmap(one)(qs, ks, v, masks))

    got = jax.grad(loss(lambda qs, ks, v, m: _flash_parts(
        qs, ks, v, m, 16, 16, window)), (0, 1, 2))(qs, ks, v)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 (qs, ks, v))
    want = jax.grad(loss(lambda qs, ks, v, m: _dense_parts(
        qs, ks, v, m, window)), (0, 1, 2))(*f32)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == jnp.bfloat16 and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                                   atol=0.03 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("window", [64, 40, None])
def test_an_interior_tile_equals_the_masked_form_to_the_last_bit(
        trips_of_four, monkeypatch, window):
    """A tile wholly under the diagonal and wholly inside the window takes
    no positional mask: every result is, bit for bit, what the kernels give
    when each tile builds it (the walk handed a ``step`` that is always told
    ``edge``)."""
    qs, ks, v, cot = _parts("grouped: 4 heads over 2 of 128", 256)
    mask = (jnp.arange(256)[None, :] < jnp.asarray([[256], [199]])
            ).astype(jnp.float32)

    def run():
        (out, lse), vjp = jax.vjp(lambda q, k, v: flash_attention_lse(
            q, k, v, mask, 16, 16, causal=True, window=window),
            qs[0], ks[0], v)
        return (out, lse, *vjp((cot, jnp.zeros_like(lse))))

    got = run()
    walk = flash_module._walk_live
    masked_steps = []

    def every_tile_masked(*args):
        *head, step, carry = args
        masked_steps.append(step)
        return walk(*head, lambda ks, c, edge: step(ks, c, True), carry)

    monkeypatch.setattr(flash_module, "_walk_live", every_tile_masked)
    want = run()
    assert len(masked_steps) == 3  # the forward, dQ, dK/dV
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_nan_behind_the_window_reaches_no_query_of_the_steady_body(
        trips_of_four):
    """The miniature's steady query blocks start their straight-line body at
    the window's far edge: a NaN in v's first key block reaches the query
    blocks whose window holds it (0 to 4) and none past them; a NaN in dO's
    last query block reaches dK / dV of the key blocks it sees (11 to 15)
    and none before."""
    qs, ks, v, cot = _parts("grouped: 4 heads over 2 of 128", 256)
    ones = jnp.ones((2, 256))
    out, vjp = jax.vjp(lambda q, k: flash_attention(
        q, k, v.at[:, :16].set(jnp.nan), ones, 16, 16, causal=True,
        window=64), qs[0], ks[0])
    dq, _ = vjp(cot)
    for x in (out, dq):
        assert np.isnan(np.asarray(x[:, :5 * 16])).any()
        assert np.isfinite(np.asarray(x[:, 5 * 16:])).all()
    _, vjp = jax.vjp(lambda k, v: flash_attention(
        qs[0], k, v, ones, 16, 16, causal=True, window=64), ks[0], v)
    for g in vjp(cot.at[:, -16:].set(jnp.nan)):
        assert np.isnan(np.asarray(g[:, 11 * 16:])).any()
        assert np.isfinite(np.asarray(g[:, :11 * 16])).all()


def _count_over_positions(t, bq, bk, window):
    """(edge, interior): executed tiles that hold a score the positional
    mask hides, and executed tiles that hold none."""
    edge = interior = 0
    for i in range(0, t, bq):
        for j in range(0, t, bk):
            seen = [0 <= a - b and (window is None or a - b < window)
                    for a in range(i, i + bq) for b in range(j, j + bk)]
            edge += any(seen) and not all(seen)
            interior += all(seen)
    return edge, interior


def test_the_traversal_counts_at_the_cells_shape():
    """A sliding head: 28 edge tiles (the diagonal's sixteen and the far
    edge's twelve) and 42 interior; a full head: 16 and 120. Block steps
    behind a scalar condition of their own: the first four query blocks' on
    a sliding layer (one digit for the far edge's tile, two for up to three
    interior ones: 4 steps each), three a query block on a full layer (the
    digits of the blocks short of a trip of four), where every one of the
    256 block steps had one. ``live_tiles`` is what it was."""
    assert traversal(8192, 512, 512, 2048) == {
        "tiles_edge": 28, "tiles_interior": 42, "cond_steps": 16}
    assert traversal(8192, 512, 512) == {
        "tiles_edge": 16, "tiles_interior": 120, "cond_steps": 48}
    assert live_tiles(8192, 512, 512, 2048) == 28 + 42
    assert live_tiles(8192, 512, 512) == 16 + 120
    # the other causal cells: T 2,048 and 1,024 at 512 / 512
    assert traversal(2048, 512, 512) == {
        "tiles_edge": 4, "tiles_interior": 6, "cond_steps": 12}
    assert traversal(1024, 512, 512) == {
        "tiles_edge": 2, "tiles_interior": 1, "cond_steps": 2}


@pytest.mark.parametrize("window", [None, 1, 2, 15, 16, 17, 20, 33, 96, 200])
@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16), (48, 32),
                                    (8, 8)])
def test_the_traversal_counts_equal_a_count_over_positions(blocks, window):
    """Forward / dQ (query block resident) and dK/dV (key block resident)
    walk the same tiles, and ``live_tiles`` counts their sum."""
    bq, bk = blocks
    want = _count_over_positions(96, bq, bk, window)
    counts = traversal(96, bq, bk, window)
    assert (counts["tiles_edge"], counts["tiles_interior"]) == want
    by_key_block, _, _ = flash_module._walk_plan(
        96 // bk, bk, bq, 96 // bq, window, False)
    assert (sum(n[0] + n[2] for n in by_key_block),
            sum(n[1] for n in by_key_block)) == want
    assert live_tiles(96, bq, bk, window) == sum(want)


def test_a_causal_call_says_its_tiles_and_conditions():
    """``count_call_sites`` holds the newest causal call's counts, under a
    window or not; a call that is not causal adds no key."""
    q, k, v, _ = _operands(4, 2, 128)
    with count_call_sites() as sites:
        flash_attention(q, k, v, MASK, BLOCK, BLOCK)
    assert sites == {"lane_indexed": 1, "transposed": 0}
    for window in (None, 24):
        with count_call_sites() as sites:
            flash_attention(q, k, v, MASK, BLOCK, BLOCK, causal=True,
                            window=window)
        counts = traversal(T, BLOCK, BLOCK, window)
        assert {k: sites[k] for k in counts} == counts
        assert sites["tiles_edge"] + sites["tiles_interior"] == live_tiles(
            T, BLOCK, BLOCK, window)


def _equations(jaxpr, name):
    """The equations of one primitive in a jaxpr, those inside its calls,
    loops and branches too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _equations(sub, name)
    return found


@pytest.mark.parametrize("window", [2048, None])
def test_the_forward_holds_fewer_conditions_than_a_head_meets(window):
    """The cell's shape, traced abstractly: the forward's kernel holds one
    condition that picks the steady body (a sliding layer) and one a binary
    digit of a run's length, no more than ``cond_steps`` says a head meets,
    where it held one a key block (16)."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, None, 512, 512, causal=True, window=window))(q, kv, kv)
    (call,) = _equations(jaxpr.jaxpr, "pallas_call")
    held = len(_equations(call.params["jaxpr"], "cond"))
    assert held == (4 if window else 2)
    assert held <= traversal(8192, 512, 512, window)["cond_steps"]


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
# takes the values anew and says so: PR 44 did for the three causal ones
# (were 9bb2e522..., 7afaaef0..., ca1caee0...), whose kernels now walk the
# live range of a query block (a key block in dK/dV) in place of a scalar
# condition a block step, build the positional mask on edge tiles alone and
# ask for a scoped-VMEM limit; the encoder's, not causal, is the parent's.
WITHOUT_A_WINDOW = {
    "encoder: 4 packed heads of 64, not causal":
        "0e10019bc5652cf64a6b31551366c7ae505aed6557327aa9e086276631d51eea",
    "jamba: 3 heads of 128 over one shared head, causal":
        "de1b05c51550f569895c118c791d04e5492ecebc5f0370a296fd68f7f85b8f7e",
    "deepseek: parts 128 + 64 over a shared rotary key, v 128, causal, scale":
        "49298b552e00b968809e619b499cbded3f3f4844e6e568641ffa11832fd8c456",
    "nemotron: 4 heads over 2 grouped heads of 128, causal":
        "9335a073e980232d24969b5ff9df11e6d040764c14cffeecab6a3fda7afab374",
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
