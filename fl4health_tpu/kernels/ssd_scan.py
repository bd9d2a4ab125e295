"""The scalar-decay state-space recurrence (Mamba-2's "SSD": Dao & Gu 2024,
arXiv:2405.21060) in its chunked form: matmuls on the MXU.

The recurrence, for head ``h`` of group ``g = h // (H / G)`` with ONE decay
``a_h < 0`` a head and a state ``S in R^{P x N}``::

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(``x_t in R^P``, ``B_t``, ``C_t in R^N`` shared by the heads of a group,
``dt_t > 0`` a head). Over a chunk of ``Q`` positions, with ``cum_t`` the
running sum of ``dt a`` inside the chunk:

- inside the chunk ``Y = (L o C B^T) (dt x)``, ``L_ts = exp(cum_t - cum_s)``
  for ``s <= t`` and 0 above the diagonal: one ``[Q, Q]`` score tile a group
  (``C B^T``), decayed per head, times the chunk's ``[Q, P]`` inputs;
- the chunk's own state ``sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T``, one
  ``[R * P, Q] x [Q, N]`` product a group (``R = H / G`` heads);
- the states carried across the chunks, ``S_in(c + 1) = exp(cum_Q(c)) S_in(c)
  + state(c)``;
- what the incoming state adds, ``exp(cum_t) C_t S_in``: one ``[Q, N] x [N,
  R * P]`` product a group.

The products take their operands in ``x``'s type (the compute type) and
accumulate in float32; ``dt``, the decays (``cum``, ``L``, every ``exp``) and
the carried state are float32 whatever the operands are. The exponent above
the diagonal is masked BEFORE ``exp`` (it is positive there and may be
large). The mixer is causal and pad positions sit at the tail, so no mask
enters; a length that is no multiple of the chunk is padded with ``dt = 0``
(a step that neither decays nor adds) and cut again.

Two paths by the operands' shapes, no knob (``_fusable``):

- **fused** where ``chunk``, ``N`` and a group's ``R * P`` are multiples of
  128 and a head is no wider than 128 lanes (the published Mamba-2 widths:
  128 / 128 / 16 x 64): two Mosaic calls under one ``custom_vjp`` (the
  interpreter on the CPU through ``_platform.py``), so no ``[Q, Q]`` tile
  ever lies in HBM, forward, recomputed forward, backward or evaluation.
  ``cum`` is summed in XLA outside them, so ``a``'s and ``dt``'s gradients
  through the sum are XLA's. Both walk a grid of (sequence, group, chunk),
  the chunk innermost and sequential, and read ``x`` as the model holds it,
  ``[B, T, H * P]`` (a group's heads are ``R * P`` contiguous lanes), ``B`` /
  ``C`` as ``[B, T, G * N]`` lane block ``g``; the per-position float32 ``dt``
  and ``cum`` come twice, as a group's columns ``[B, G, T, R]`` (a position
  along the sublanes: what scales a row) and ``cum`` also as rows ``[B,
  T / Q, G, R, Q]`` (a position along the lanes: the tile's other index), 4 MB
  a layer each. A grid step holds one group's chunk: ``C B^T`` once, then
  per 128-lane tile of ``x`` (two heads of 64) each head's masked ``exp``,
  scores and ``scores (dt x)`` under a lane mask, merged by lane.
  - ``ssd_chunk_fwd`` carries the group's state ``[N, R * P]`` float32 (512
    kB) in VMEM scratch across the chunks, zeroed at a sequence's first;
    under differentiation it also writes the state at every chunk's START
    (``[B, T / Q, G, N, R * P]`` float32: with the operands, all the backward
    reads).
  - ``ssd_chunk_bwd`` walks the chunks backwards with the state's adjoint in
    the scratch. It rebuilds each head's tile TRANSPOSED (``[s, t]``, so ``dx
    += S^T dy`` is a plain product), and writes ``dx``, ``d(dt)`` and
    ``d(cum)`` (the tile's row sums as columns, its column sums as rows),
    ``dB`` and ``dC`` with a group's heads summed in float32 inside the call.
- **xla** for everything else (toy widths): ``ssd_scan_xla``, plain ``jnp`` /
  ``lax`` with the tiles as arrays, the oracle the fused path is tested
  against.

``count_call_sites`` says which path a traced call took. Both run under
``vmap``, ``jax.checkpoint`` and ``grad``; every op, forward, recomputed
forward and backward, carries the ``fl_layer::ssd_scan`` name scope.
"""

from __future__ import annotations

import collections
import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fl4health_tpu.kernels._platform import interpret_default
# heads that share a 128-lane tile: the flash calls' masks, mask and merge
from fl4health_tpu.kernels.flash_attention import _head_lanes, _merge, _only
from fl4health_tpu.observability.stages import layer as part

F32 = jnp.float32
_LANE = 128


def n_chunks(t: int, chunk: int) -> int:
    return -(-t // chunk)


# ssd_scan calls traced while a counter is open, by the path each took: a
# fact of the traced program, for a build-time gauge
_site_counters: list = []


@contextlib.contextmanager
def count_call_sites():
    """Counts, while open, the ``ssd_scan`` calls TRACED, by path:
    ``{"fused": n, "xla": m}``. A run of blocks under ``lax.scan`` traces its
    call once."""
    sites = collections.Counter(fused=0, xla=0)
    _site_counters.append(sites)
    try:
        yield sites
    finally:
        _site_counters.remove(sites)


def _fusable(chunk: int, n: int, r: int, p: int) -> bool:
    """The Mosaic calls take whole 128-lane tiles: of a chunk's positions, of
    the state and of a group's heads, a head inside one tile."""
    return (chunk % _LANE == 0 and n % _LANE == 0 and (r * p) % _LANE == 0
            and _LANE % p == 0)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """x [B, T, H, P] (the compute type), dt [B, T, H] float32 (after
    softplus), a [H] float32 (negative), b / c [B, T, G, N] -> y [B, T, H, P]
    float32, ``y_t = S_t C_t`` of the recurrence above (the caller adds the
    skip ``D x``)."""
    h, p = x.shape[2:]
    g, n = b.shape[2:]
    path = "fused" if _fusable(chunk, n, h // g, p) else "xla"
    for sites in _site_counters:
        sites[path] += 1
    if path == "xla":
        return ssd_scan_xla(x, dt, a, b, c, chunk)
    with part("ssd_scan"):
        bsz, t = x.shape[:2]
        pad = -t % chunk
        x, b, c = (v.reshape(bsz, t, -1) for v in (x, b, c))
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                           for v in (x, dt, b, c))
        dt = dt.astype(F32)
        cum = jnp.cumsum((dt * a.astype(F32)).reshape(bsz, -1, chunk, h),
                         axis=2).reshape(dt.shape)
        y = _fused(x, dt, cum, b, c, chunk, g, interpret_default())
        return y[:, :t].reshape(bsz, t, h, p)


def ssd_scan_xla(x, dt, a, b, c, chunk: int):
    """``ssd_scan`` with every tile an array of its own: what widths off the
    128-lane tiles run, and what the fused path is held to."""
    with part("ssd_scan"):
        bsz, t, h, p = x.shape
        g, n = b.shape[2:]
        r, dtype = h // g, x.dtype
        pad = -t % chunk
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                   * (v.ndim - 2)) for v in (x, dt, b, c))
        nc = (t + pad) // chunk
        x = x.reshape(bsz, nc, chunk, g, r, p)
        dt = dt.astype(F32).reshape(bsz, nc, chunk, g, r)
        b = b.reshape(bsz, nc, chunk, g, n)
        c = c.reshape(bsz, nc, chunk, g, n)
        # cum_t = sum_{r <= t} dt_r a: [B, nc, Q, G, R], float32
        cum = jnp.cumsum(dt * a.astype(F32).reshape(g, r), axis=2)
        cum_h = jnp.transpose(cum, (0, 1, 3, 4, 2))  # [B, nc, G, R, Q]
        on_or_under = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
        decay = jnp.exp(jnp.where(
            on_or_under, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", c, b,
                        preferred_element_type=F32)
        scores = (decay * cb[:, :, :, None]).astype(dtype)
        xf = x.astype(F32)
        xdt = (xf * dt[..., None]).astype(dtype)
        y = jnp.einsum("bcgrts,bcsgrp->bctgrp", scores, xdt,
                       preferred_element_type=F32)
        # the chunk's own state and what the chunk leaves of an older one
        to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, Q, G, R]
        xw = (xf * (dt * to_end)[..., None]).astype(dtype)
        states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, b,
                            preferred_element_type=F32)
        total = jnp.exp(cum[:, :, -1])  # [B, nc, G, R]

        def carry(s_in, chunk_c):
            state, keep = chunk_c
            return s_in * keep[..., None, None] + state, s_in

        _, s_in = jax.lax.scan(
            carry, jnp.zeros((bsz, g, r, p, n), F32),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
        s_in = jnp.moveaxis(s_in, 0, 1).astype(dtype)  # [B, nc, G, R, P, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bctgn,bcgrpn->bctgrp", c, s_in, preferred_element_type=F32)
        return y.reshape(bsz, t + pad, h, p)[:, :t]


# ---------------------------------------------------------------------------
# The fused path: what a grid step does with one group's chunk
# ---------------------------------------------------------------------------

def _dot(a, b, contract, like):
    """a . b over ``contract`` = (axis of a, axis of b): operands in the
    compute type ``like`` (a float32 tile computed in the call is cast, as
    the ``jnp`` form casts it), float32 accumulation. float32 operands get
    faithful float32 products (Mosaic's default is ONE bfloat16 pass)."""
    return jax.lax.dot_general(
        a.astype(like), b.astype(like),
        (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=F32,
        precision=(jax.lax.Precision.HIGHEST if like == F32
                   else jax.lax.Precision.DEFAULT))


def _turned(tile):
    """[Q, N] -> [N, Q] of an operand tile, exactly, through float32 (the
    transpose unit's own width)."""
    return jnp.transpose(tile.astype(F32)).astype(tile.dtype)


def _spread(cols, heads, owns):
    """Per-head values ``cols[:, h]`` ([Q, R] columns or a [1, R] row) along
    a 128-lane tile: the lanes ``owns[i]`` hold head ``heads[i]``'s."""
    return _merge([cols[:, h:h + 1] for h in heads], owns)


def _head_sum(tile, own):
    """[Q, 128] -> [Q, 1]: the sum over one head's lanes."""
    return jnp.sum(_only(tile, own), axis=1, keepdims=True)


def _columns(cols, width):
    """[Q, 1] a head -> [Q, R]: column ``h`` is ``cols[h]``."""
    out = jnp.zeros((cols[0].shape[0], width), F32)
    for h, col in enumerate(cols):
        out = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) == h, col, out)
    return out


def _decay(cum_t, cum_s, live):
    """exp(cum_t - cum_s) where ``live`` (on or under the diagonal), 0 above:
    masked before ``exp``."""
    return jnp.exp(jnp.where(live, cum_t - cum_s, -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_row_ref, y_ref,
                *rest, p):
    sin_ref, s_scr = (rest if len(rest) == 2 else (None, *rest))
    dtype = x_ref.dtype
    q, rp = x_ref.shape[1:]
    k = _LANE // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, F32)

    if sin_ref is not None:
        sin_ref[0, 0, 0] = s_scr[...]
    b, c = b_ref[0], c_ref[0]
    cb = _dot(c, b, (1, 1), dtype)  # [t, s]
    b_t = _turned(b)  # [N, s]
    dt, cum = dt_ref[0, 0], cum_ref[0, 0]  # [Q, R]
    last = cum[q - 1:q]
    into = jnp.exp(cum)  # what a position keeps of the incoming state
    weigh = dt * jnp.exp(last - cum)  # dt_s exp(cum_Q - cum_s)
    total = jnp.exp(last)  # [1, R]
    live = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    owns = _head_lanes(k, 1)  # [1, 128] a head of a tile; [None] at one
    for j in range(rp // _LANE):
        lanes = slice(j * _LANE, (j + 1) * _LANE)
        heads = range(j * k, (j + 1) * k)
        xf = x_ref[0, :, lanes].astype(F32)
        xdt = (xf * _spread(dt, heads, owns)).astype(dtype)
        s_in = s_scr[:, lanes]
        parts = [_dot(_decay(cum[:, h:h + 1], cum_row_ref[0, 0, 0, h:h + 1],
                             live) * cb, xdt, (1, 0), dtype) for h in heads]
        y_ref[0, :, lanes] = _merge(parts, owns) + _spread(
            into, heads, owns) * _dot(c, s_in, (1, 0), dtype)
        xw = (xf * _spread(weigh, heads, owns)).astype(dtype)
        s_scr[:, lanes] = (_spread(total, heads, owns) * s_in
                           + _dot(b_t, xw, (1, 0), dtype))


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_row_ref, sin_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref,
                dcum_row_ref, w_scr, *, p):
    dtype = x_ref.dtype
    q, rp = x_ref.shape[1:]
    k, r = _LANE // p, dt_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)  # the LAST chunk: they run backwards
    def _():
        w_scr[...] = jnp.zeros(w_scr.shape, F32)

    b, c = b_ref[0], c_ref[0]
    cb_t = _dot(b, c, (1, 1), dtype)  # [s, t]
    c_t = _turned(c)  # [N, t]
    dt, cum = dt_ref[0, 0], cum_ref[0, 0]  # [Q, R]
    last = cum[q - 1:q]
    into = jnp.exp(cum)
    to_end = jnp.exp(last - cum)
    total = jnp.exp(last)  # [1, R]
    live = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    owns = _head_lanes(k, 1)  # [1, 128] a head of a tile; [None] at one
    dcb_t = jnp.zeros((q, q), F32)
    db = jnp.zeros(b.shape, F32)
    dc = jnp.zeros(c.shape, F32)
    ddt_cols, dcum_cols, dlast = [], [], []
    for j in range(rp // _LANE):
        lanes = slice(j * _LANE, (j + 1) * _LANE)
        heads = range(j * k, (j + 1) * k)
        xf = x_ref[0, :, lanes].astype(F32)
        dy = dy_ref[0, :, lanes].astype(F32)
        dy_c = dy.astype(dtype)
        dt_l, end_l = _spread(dt, heads, owns), _spread(to_end, heads, owns)
        into_l = _spread(into, heads, owns)
        xdt = (xf * dt_l).astype(dtype)
        xw = (xf * (dt_l * end_l)).astype(dtype)
        s_in, w = sin_ref[0, 0, 0, :, lanes], w_scr[:, lanes]
        s_in_c, w_c = s_in.astype(dtype), w.astype(dtype)
        dy_in = (into_l * dy).astype(dtype)
        # through the chunk's tiles, a head at a time
        dxdt = []
        for own, h in zip(owns, heads):
            decay = _decay(cum_row_ref[0, 0, 0, h:h + 1], cum[:, h:h + 1], live)
            pull = _dot(_only(xdt, own), dy_c, (1, 1), dtype) * decay
            dcb_t = dcb_t + pull
            pull = pull * cb_t  # dS o S: what moves with either cum
            dcum_row_ref[0, 0, 0, h:h + 1] = jnp.sum(pull, axis=0, keepdims=True)
            dcum_cols.append(-jnp.sum(pull, axis=1, keepdims=True))
            dxdt.append(_dot(decay * cb_t, dy_c, (1, 0), dtype))
        # through the chunk's own state and the incoming state's part of y
        dxw = _dot(b, w_c, (1, 0), dtype)
        both = _merge(dxdt, owns) + dxw * end_l
        dx_ref[0, :, lanes] = (dt_l * both).astype(dx_ref.dtype)
        moved = xf * dxw * (dt_l * end_l)  # d(to_end) to_end, a lane
        kept = dy * into_l * _dot(c, s_in_c, (1, 0), dtype)  # d(into) into
        carried = jnp.sum(w * s_in, axis=0, keepdims=True)  # [1, 128]
        for own, h in zip(owns, heads):
            ddt_cols.append(_head_sum(xf * both, own))
            at_end = _head_sum(moved, own)
            dcum_cols[h] = dcum_cols[h] + _head_sum(kept, own) - at_end
            dlast.append(jnp.sum(at_end, axis=0, keepdims=True)
                         + total[:, h:h + 1] * _head_sum(carried, own))
        dc = dc + _dot(dy_in, s_in_c, (1, 1), dtype)
        db = db + _dot(xw, w_c, (1, 1), dtype)
        w_scr[:, lanes] = (_spread(total, heads, owns) * w
                           + _dot(c_t, dy_in, (1, 0), dtype))
    dc_ref[0] = (dc + _dot(jnp.transpose(dcb_t), b, (1, 0), dtype)).astype(
        dc_ref.dtype)
    db_ref[0] = (db + _dot(dcb_t, c, (1, 0), dtype)).astype(db_ref.dtype)
    ddt_ref[0, 0] = _columns(ddt_cols, r)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, r), 0) == q - 1
    dcum_ref[0, 0] = _columns(dcum_cols, r) + jnp.where(
        at_last, _columns(dlast, r), 0.0)


# (sequence, group, chunk): sequences and groups are independent; the state
# (its adjoint) runs through the chunks in VMEM scratch
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def _specs(x, b, dt_cols, chunk, g, at):
    """The blocks of one group's chunk, the chunk step ``j`` reading chunk
    ``at(j)``: x / y / dx ``[1, Q, R * P]`` and B / C ``[1, Q, N]`` at lane
    block ``g``, the columns ``[1, 1, Q, R]``, the rows ``[1, 1, 1, R, Q]`` and
    the state ``[1, 1, 1, N, R * P]``."""
    rp, n, r = x.shape[2] // g, b.shape[2] // g, dt_cols.shape[3]
    seq = lambda w: pl.BlockSpec(  # noqa: E731
        (1, chunk, w), lambda i, gi, j: (i, at(j), gi))
    return (seq(rp), seq(n),
            pl.BlockSpec((1, 1, chunk, r), lambda i, gi, j: (i, gi, at(j), 0)),
            pl.BlockSpec((1, 1, 1, r, chunk),
                         lambda i, gi, j: (i, at(j), gi, 0, 0)),
            pl.BlockSpec((1, 1, 1, n, rp),
                         lambda i, gi, j: (i, at(j), gi, 0, 0)))


def _fwd_call(x, b, c, dt_cols, cum_cols, cum_rows, chunk, g, keep, interpret):
    bsz, t, hp = x.shape
    nc, h = t // chunk, g * dt_cols.shape[3]
    rp, n = hp // g, b.shape[2] // g
    wide, state, cols, rows, carried = _specs(x, b, dt_cols, chunk, g,
                                              lambda j: j)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=hp // h),
        grid=(bsz, g, nc),
        in_specs=[wide, state, state, cols, cols, rows],
        out_specs=[wide] + [carried] * keep,
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32)]
        + [jax.ShapeDtypeStruct((bsz, nc, g, n, rp), F32)] * keep,
        scratch_shapes=[pltpu.VMEM((n, rp), F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, b, c, dt_cols, cum_cols, cum_rows)
    return out if keep else (out[0], None)


def _bwd_call(x, b, c, dt_cols, cum_cols, cum_rows, s_in, dy, chunk, g,
              interpret):
    bsz, t, hp = x.shape
    nc, h = t // chunk, g * dt_cols.shape[3]
    rp, n = hp // g, b.shape[2] // g
    wide, state, cols, rows, carried = _specs(x, b, dt_cols, chunk, g,
                                              lambda j: nc - 1 - j)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=hp // h),
        grid=(bsz, g, nc),
        in_specs=[wide, state, state, cols, cols, rows, carried, wide],
        out_specs=[wide, state, state, cols, cols, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(dt_cols.shape, F32),
                   jax.ShapeDtypeStruct(dt_cols.shape, F32),
                   jax.ShapeDtypeStruct(cum_rows.shape, F32)],
        scratch_shapes=[pltpu.VMEM((n, rp), F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, b, c, dt_cols, cum_cols, cum_rows, s_in, dy)


def _as_cols(v, g):
    """[B, T, H] -> [B, G, T, R]: a group's heads side by side, a position a
    sublane."""
    bsz, t, h = v.shape
    return jnp.transpose(v.reshape(bsz, t, g, h // g), (0, 2, 1, 3))


def _from_cols(v):
    bsz, g, t, r = v.shape
    return jnp.transpose(v, (0, 2, 1, 3)).reshape(bsz, t, g * r)


def _as_rows(v, chunk, g):
    """[B, T, H] -> [B, T / Q, G, R, Q]: a chunk's positions along the
    lanes."""
    bsz, t, h = v.shape
    return jnp.transpose(v.reshape(bsz, t // chunk, chunk, g, h // g),
                         (0, 1, 3, 4, 2))


def _from_rows(v):
    bsz, nc, g, r, chunk = v.shape
    return jnp.transpose(v, (0, 1, 4, 2, 3)).reshape(bsz, nc * chunk, g * r)


def _layouts(dt, cum, chunk, g):
    """What the calls read of the per-position float32 values: ``dt`` and
    ``cum`` as columns, ``cum`` also as rows."""
    return _as_cols(dt, g), _as_cols(cum, g), _as_rows(cum, chunk, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(x, dt, cum, b, c, chunk, g, interpret):
    """x [B, T, H * P], dt / cum [B, T, H] float32, b / c [B, T, G * N], T a
    multiple of ``chunk`` -> y [B, T, H * P] float32."""
    return _fwd_call(x, b, c, *_layouts(dt, cum, chunk, g), chunk, g, False,
                     interpret)[0]


def _fused_fwd(x, dt, cum, b, c, chunk, g, interpret):
    y, s_in = _fwd_call(x, b, c, *_layouts(dt, cum, chunk, g), chunk, g, True,
                        interpret)
    return y, (x, dt, cum, b, c, s_in)


def _fused_bwd(chunk, g, interpret, res, dy):
    x, dt, cum, b, c, s_in = res
    dx, db, dc, ddt, dcum, dcum_rows = _bwd_call(
        x, b, c, *_layouts(dt, cum, chunk, g), s_in, dy, chunk, g, interpret)
    return (dx, _from_cols(ddt), _from_cols(dcum) + _from_rows(dcum_rows),
            db, dc)


_fused.defvjp(_fused_fwd, _fused_bwd)
