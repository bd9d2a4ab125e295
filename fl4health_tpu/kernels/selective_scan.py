"""Selective state-space scan (Mamba-1, Gu & Dao 2023, arXiv:2312.00752
eq. 2 with input-dependent Delta, B, C) — the recurrence of a Mamba mixer:

    s_t = exp(Delta_t * A) * s_{t-1} + (Delta_t * x_t) (x) B_t
    y_t = s_t . C_t + D * x_t            out_t = y_t * silu(z_t)

with ``s`` a float32 ``[d_inner, d_state]`` matrix per sequence. Written out
over all positions the state is ``[T, d_inner, d_state]`` (328 KB a token in
float32 at d_inner 5,120 and d_state 16: 2.7 GB a layer at 8,192 tokens), so
it never reaches HBM, neither forward nor for the backward pass.

One implementation, no switch: two Pallas kernels under a ``custom_vjp``
(Mosaic on the TPU, the interpreter on the CPU through ``_platform.py``).
Both walk a grid of (sequence, block of ``block_t`` positions, block of
``d_inner`` channels) with the CHANNEL axis innermost: ``B`` and ``C`` (and
``dB`` / ``dC``) have no channel axis, so their block of a (sequence, time
block) keeps its index over the channel steps and crosses between HBM and
VMEM once, not once per channel block. The price is that time no longer
runs innermost: the state of EVERY channel block of the sequence,
``[d_inner / channels, d_state, channels]`` (channels along the lanes, 328 KB
at 8 x 16 x 640), waits in VMEM scratch while the others step through the
same time block, picked by the channel step's ``program_id`` and zeroed at
the sequence's first time block.

- ``ssm_scan_fwd`` steps through the positions of a block, writes ``y`` and
  the state at the START of every time block (``[T / block_t, d_state,
  d_inner]`` float32: all that is kept for the backward pass).
- ``ssm_scan_bwd`` walks the time blocks backwards: it recomputes the
  block's states from its boundary state into VMEM, then runs the adjoint
  recurrence through the block. Gradients that sum over channels (dB, dC)
  are summed over the channel blocks IN the kernel: one ``[block_t, d_state,
  128]`` float32 block of per-lane partial sums per (sequence, time block),
  resident over the channel steps, zeroed at the first and written once, so
  they leave the chip at ``B``'s own lane-splat size and XLA folds 128 lanes
  only. The adjoint state and dA and dD accumulate in VMEM scratch per
  channel block over the whole sequence; their output tiles come round once
  a time block and every visit leaves the whole sum so far.

Bytes a pass at the adapter cell's call (4 x 2,048 positions x 5,120
channels x 16 states, bf16; 32 time blocks x 8 channel blocks), by the
``BlockSpec``s: forward 0.25 GB of x, Delta, z + 0.13 GB of B, C + 0.13 GB
of y and boundary states = 0.51 GB (1.45 GB with time innermost: B and C
eight times); backward 0.38 GB in + 0.13 GB of B, C + 0.25 GB of dx, dDelta,
dz + 0.13 GB of dB, dC = 0.90 GB (2.78 GB, and 1.07 GB more for XLA's fold).
A and D are fetched per step (42 MB a pass), dA and dD written per step
(42 MB).

``B`` and ``C`` enter with each value repeated along a row of 128 lanes
(``[T, d_state, 128]``), so a position's ``[d_state, 128]`` tile meets every
128-lane chunk of the state with no broadcast in the kernel. The recurrence,
its state and every accumulator are float32 whatever the operands are. It
runs under ``vmap`` (the engine's clients axis), ``jax.checkpoint`` (a
rematerialised layer) and ``grad``. Every op, forward, recomputed forward and
backward, carries the ``fl_layer::ssm_scan`` name scope, which is how a
device trace tells the scan's share of a round
(benchmarks/layer_metrics/ssm_scan_ms_per_round.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fl4health_tpu.kernels._platform import interpret_default
from fl4health_tpu.observability import stages

SCOPE = stages.LAYER_PREFIX + "ssm_scan"
_LANE = 128
F32 = jnp.float32


def _lanes(tile, k):
    """[N, 128] -> [N, k * 128]: the same vregs along the lanes."""
    return tile if k == 1 else jnp.concatenate([tile] * k, axis=1)


def _fold(full, k):
    """[N, k * 128] -> [N, 128]: the sum of the k lane chunks."""
    lane = full.shape[1] // k
    out = full[:, :lane]
    for i in range(1, k):
        out = out + full[:, i * lane:(i + 1) * lane]
    return out


def _loop(n, unroll, body, carry):
    """carry = body(t, carry) for t in range(n), ``unroll`` positions a trip.
    A trip is traced once and laid out as one straight-line body (Mosaic's
    own ``unroll`` takes 1 or all); a block of one trip has no loop at all
    and its positions are constants."""
    def trip(i, c):
        return jax.lax.fori_loop(
            0, unroll, lambda u, c: body(i * unroll + u, c), c, unroll=True)

    if n == unroll:
        return trip(0, carry)
    return jax.lax.fori_loop(0, n // unroll, trip, carry)


def _advance(t, s, x32, dt32, a, b_ref, c_ref, y32, k):
    """One position of the recurrence: the new state, with ``s . C_t`` written
    to row ``t`` of ``y32``."""
    x_t = x32[pl.ds(t, 1), :]
    dt_t = dt32[pl.ds(t, 1), :]
    s = jnp.exp(dt_t * a) * s + (dt_t * x_t) * _lanes(b_ref[0, t], k)
    y32[pl.ds(t, 1), :] = jnp.sum(s * _lanes(c_ref[0, t], k), axis=0,
                                  keepdims=True)
    return s


def _fwd_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                y_ref, sb_ref, s_scr, x32, dt32, y32, *, tb, unroll):
    k = x32.shape[1] // b_ref.shape[-1]
    ch = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[ch] = jnp.zeros(s_scr.shape[1:], F32)

    sb_ref[0, 0] = s_scr[ch]
    x32[...] = x_ref[0].astype(F32)
    dt32[...] = dt_ref[0].astype(F32)
    a = a_ref[...]

    def step(t, s):
        return _advance(t, s, x32, dt32, a, b_ref, c_ref, y32, k)

    s_scr[ch] = _loop(tb, unroll, step, s_scr[ch])
    y = y32[...] + d_ref[...] * x32[...]
    y_ref[0] = (y * jax.nn.silu(z_ref[0].astype(F32))).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, sb_ref,
                dy_ref,
                dx_ref, ddt_ref, dz_ref, db_ref, dc_ref, da_ref, dd_ref,
                w_scr, da_scr, dd_scr, s_all, x32, dt32, g32, y32, dx32,
                ddt32, *, tb, unroll):
    k = x32.shape[1] // b_ref.shape[-1]
    ch = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)  # the LAST time block: time runs backwards
    def _():
        w_scr[ch] = jnp.zeros(w_scr.shape[1:], F32)
        da_scr[ch] = jnp.zeros(da_scr.shape[1:], F32)
        dd_scr[ch] = jnp.zeros(dd_scr.shape[1:], F32)

    @pl.when(ch == 0)  # dB and dC sum over the channel blocks of a time block
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    x32[...] = x_ref[0].astype(F32)
    dt32[...] = dt_ref[0].astype(F32)
    a = a_ref[...]
    z = z_ref[0].astype(F32)
    sig = jax.nn.sigmoid(z)
    dy = dy_ref[0].astype(F32)
    g32[...] = dy * (z * sig)  # cotangent of y_pre

    # 1. the block's states again, from its boundary state; slot t + 1 holds
    #    s_t, slot 0 the boundary
    s_all[0] = sb_ref[0, 0]

    def forward(t, s):
        s = _advance(t, s, x32, dt32, a, b_ref, c_ref, y32, k)
        s_all[t + 1] = s
        return s

    _loop(tb, unroll, forward, s_all[0])
    y_pre = y32[...] + d_ref[...] * x32[...]
    dz_ref[0] = (dy * y_pre * (sig * (1.0 + z * (1.0 - sig)))).astype(
        dz_ref.dtype)

    # 2. backwards through the block; w = a_{t+1} * adj_{t+1}
    def backward(i, carry):
        w, da = carry
        t = tb - 1 - i
        x_t = x32[pl.ds(t, 1), :]
        dt_t = dt32[pl.ds(t, 1), :]
        g_t = g32[pl.ds(t, 1), :]
        b_t = _lanes(b_ref[0, t], k)
        adj = g_t * _lanes(c_ref[0, t], k) + w
        decay = jnp.exp(dt_t * a)
        w = adj * decay
        pull = w * s_all[t]  # adj * a_t * s_{t-1}
        da = da + pull * dt_t
        s1 = jnp.sum(adj * b_t, axis=0, keepdims=True)
        s2 = jnp.sum(pull * a, axis=0, keepdims=True)
        ddt32[pl.ds(t, 1), :] = s2 + x_t * s1
        dx32[pl.ds(t, 1), :] = dt_t * s1
        db_ref[0, t] += _fold(adj * (dt_t * x_t), k)
        dc_ref[0, t] += _fold(g_t * s_all[t + 1], k)
        return w, da

    w, da = _loop(tb, unroll, backward, (w_scr[ch], da_scr[ch]))
    dd = dd_scr[ch] + jnp.sum(g32[...] * x32[...], axis=0, keepdims=True)
    w_scr[ch] = w
    da_scr[ch] = da
    dd_scr[ch] = dd
    dx_ref[0] = (dx32[...] + d_ref[...] * g32[...]).astype(dx_ref.dtype)
    ddt_ref[0] = ddt32[...].astype(ddt_ref.dtype)
    # the (sequence, channel block) tiles of dA and dD come round once a time
    # block: every visit leaves the whole sum so far, the last one the sum
    da_ref[0] = da
    dd_ref[0] = dd


def _channel_block(d_inner: int, interpret: bool) -> int:
    """Channels a grid step holds: the widest of these that divides d_inner
    (5,120 = 8 x 640; 384 or 256 are ONE block, 896 is seven of 128). The
    backward kernel keeps block_t + 1 states of [d_state, channels] in VMEM,
    2.7 MB at 16 x 640 x 65, beside the state, adjoint state and dA of every
    channel block (0.33 MB each at 5,120 channels). B and C cost the same
    whatever the width, so a narrower block only buys more grid steps. The
    interpreter (CPU) takes a width off the lanes as one block."""
    if d_inner % _LANE:
        if interpret:
            return d_inner
        raise ValueError(f"selective_scan: d_inner={d_inner} is not a "
                         f"multiple of the {_LANE}-lane width; the compiled "
                         "(Mosaic) kernel takes no other")
    return next(dk for dk in (640, 512, 384, 256, 128) if d_inner % dk == 0)


# (sequence, time block, channel block): sequences are independent; the state
# runs through the time blocks and dB / dC sum over the channel blocks. VMEM:
# of the v5e's 128 MiB; the backward holds 11 MB of blocks and scratch at
# BLOCK_T 64, and a block unrolled whole spills its temporaries beside them
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def _fwd_call(x, dt, z, b_exp, c_exp, a_t, d_row, tb, dk, unroll, interpret):
    bsz, t, d_inner = x.shape
    n = a_t.shape[0]
    n_ch = d_inner // dk
    grid = (bsz, t // tb, n_ch)
    seq = pl.BlockSpec((1, tb, dk), lambda i, j, c: (i, j, c))
    lane = b_exp.shape[-1]
    bc = pl.BlockSpec((1, tb, n, lane), lambda i, j, c: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tb=tb, unroll=unroll),
        grid=grid,
        in_specs=[seq, seq, seq, bc, bc,
                  pl.BlockSpec((n, dk), lambda i, j, c: (0, c)),
                  pl.BlockSpec((1, dk), lambda i, j, c: (0, c))],
        out_specs=[seq,
                   pl.BlockSpec((1, 1, n, dk), lambda i, j, c: (i, j, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, t // tb, n, d_inner), F32)],
        scratch_shapes=[pltpu.VMEM((n_ch, n, dk), F32),
                        pltpu.VMEM((tb, dk), F32), pltpu.VMEM((tb, dk), F32),
                        pltpu.VMEM((tb, dk), F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssm_scan_fwd",
    )(x, dt, z, b_exp, c_exp, a_t, d_row)


def _bwd_call(x, dt, z, b_exp, c_exp, a_t, d_row, s_bound, dy, tb, dk, unroll,
              interpret):
    bsz, t, d_inner = x.shape
    n = a_t.shape[0]
    n_tb, n_ch = t // tb, d_inner // dk
    grid = (bsz, n_tb, n_ch)
    rev = lambda j: n_tb - 1 - j  # noqa: E731
    seq = pl.BlockSpec((1, tb, dk), lambda i, j, c: (i, rev(j), c))
    lane = b_exp.shape[-1]
    bc = pl.BlockSpec((1, tb, n, lane), lambda i, j, c: (i, rev(j), 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tb=tb, unroll=unroll),
        grid=grid,
        in_specs=[seq, seq, seq, bc, bc,
                  pl.BlockSpec((n, dk), lambda i, j, c: (0, c)),
                  pl.BlockSpec((1, dk), lambda i, j, c: (0, c)),
                  pl.BlockSpec((1, 1, n, dk),
                               lambda i, j, c: (i, rev(j), 0, c)),
                  seq],
        out_specs=[seq, seq, seq, bc, bc,
                   pl.BlockSpec((1, n, dk), lambda i, j, c: (i, 0, c)),
                   pl.BlockSpec((1, 1, dk), lambda i, j, c: (i, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, dt.dtype),
                   jax.ShapeDtypeStruct(x.shape, z.dtype),
                   jax.ShapeDtypeStruct(b_exp.shape, F32),
                   jax.ShapeDtypeStruct(c_exp.shape, F32),
                   jax.ShapeDtypeStruct((bsz, n, d_inner), F32),
                   jax.ShapeDtypeStruct((bsz, 1, d_inner), F32)],
        scratch_shapes=[pltpu.VMEM((n_ch, n, dk), F32),
                        pltpu.VMEM((n_ch, n, dk), F32),
                        pltpu.VMEM((n_ch, 1, dk), F32),
                        pltpu.VMEM((tb + 1, n, dk), F32)]
        + [pltpu.VMEM((tb, dk), F32)] * 6,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssm_scan_bwd",
    )(x, dt, z, b_exp, c_exp, a_t, d_row, s_bound, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _scan(x, dt, z, b, c, a, d, tb, dk, unroll, interpret):
    return _scan_fwd(x, dt, z, b, c, a, d, tb, dk, unroll, interpret)[0]


def _expand(v, dk):
    """[B, T, N] -> [B, T, N, 128]: each value along a row of lanes."""
    lane = _LANE if dk % _LANE == 0 else dk
    return jnp.broadcast_to(v.astype(F32)[..., None], (*v.shape, lane))


def _kernel_operands(b, c, a, d, dk):
    """B and C lane-splat, A as [d_state, d_inner], D as a row, float32."""
    return (_expand(b, dk), _expand(c, dk), jnp.transpose(a).astype(F32),
            d.astype(F32)[None, :])


def _scan_fwd(x, dt, z, b, c, a, d, tb, dk, unroll, interpret):
    y, s_bound = _fwd_call(x, dt, z, *_kernel_operands(b, c, a, d, dk), tb,
                           dk, unroll, interpret)
    return y, (x, dt, z, b, c, a, d, s_bound)


def _scan_bwd(tb, dk, unroll, interpret, res, dy):
    x, dt, z, b, c, a, d, s_bound = res
    dx, ddt, dz, db, dc, da, dd = _bwd_call(
        x, dt, z, *_kernel_operands(b, c, a, d, dk), s_bound, dy, tb, dk,
        unroll, interpret)
    db = jnp.sum(db, axis=-1).astype(b.dtype)
    dc = jnp.sum(dc, axis=-1).astype(c.dtype)
    da = jnp.transpose(jnp.sum(da, axis=0)).astype(a.dtype)
    dd = jnp.sum(dd, axis=(0, 1)).astype(d.dtype)
    return dx, ddt, dz, db, dc, da, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


# Positions a grid step holds and positions a loop trip steps through. On the
# v5e, bf16 at 4 x 2,048 x 5,120 x 16 (PR 30), ms of device self time of the
# forward / backward kernel alone by (BLOCK_T, UNROLL): (32, 16) 1.995 / 5.454,
# (32, 32) 1.949 / 5.133, (64, 8) 1.881 / 5.427, (64, 16) 1.799 / 5.259,
# (64, 32) 1.768 / 5.163, (64, 64) 1.731 / 4.924, (128, 16) 1.757 / 5.231,
# (128, 32) 1.723 / 5.142, (128, 64) 1.706 / 5.108, (256, 32) 1.733 / 5.112,
# (256, 64) 1.716 / 5.082. A block with no loop left is what pays in the
# backward; a longer block saves the forward 0.18 us a grid step. The backward
# at UNROLL 64 or BLOCK_T 128 needs more than the 16 MiB of VMEM a kernel gets
# unasked (_COMPILER_PARAMS).
BLOCK_T = 64
UNROLL = 64


def _blocked_scan(x, dt, a, b, c, d, z, block_t, unroll, interpret):
    """``selective_scan`` at a given time block (a multiple of ``unroll``,
    and of 16 for 16-bit operands)."""
    with stages.layer("ssm_scan"):
        t = x.shape[1]
        # a padded position has Delta = 0 and x = 0 and leaves s as it is
        pad = (-t) % block_t
        if pad:
            x, dt, z, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                              for v in (x, dt, z, b, c))
        y = _scan(x, dt, z, b, c, a, d, block_t,
                  _channel_block(x.shape[2], interpret), unroll, interpret)
        return y[:, :t]


def selective_scan(x, dt, a, b, c, d, z):
    """x, dt, z: [B, T, d_inner] (any float type); a: [d_inner, d_state]
    (negative); b, c: [B, T, d_state]; d: [d_inner]. Returns
    ``(scan(x) + d * x) * silu(z)`` as [B, T, d_inner] in ``x.dtype``. On
    the TPU ``d_inner`` must be a multiple of 128."""
    interpret = interpret_default()
    # a straight-line block is Mosaic's to schedule; the interpreter would
    # only hand XLA:CPU 64 copies of the body to compile
    return _blocked_scan(x, dt, a, b, c, d, z, BLOCK_T,
                         1 if interpret else UNROLL, interpret)


def selective_scan_reference(x, dt, a, b, c, d, z):
    """The same function as one sequential float32 ``lax.scan`` over the
    positions (what the tests hold ``selective_scan`` to)."""
    f32 = lambda u: u.astype(F32)  # noqa: E731
    x, dt, b, c, z = f32(x), f32(dt), f32(b), f32(c), f32(z)

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.einsum("bdn,bn->bd", s, c_t)

    time_major = lambda u: jnp.swapaxes(u, 0, 1)  # noqa: E731
    s0 = jnp.zeros((x.shape[0], *a.shape), F32)
    _, y = jax.lax.scan(step, s0, tuple(map(time_major, (x, dt, b, c))))
    return (time_major(y) + d * x) * jax.nn.silu(z)
