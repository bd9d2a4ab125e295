"""Pallas flash-attention kernel — fused softmax attention for the
transformer hot path.

The XLA path (parallel/ring_attention.py ``_dense_attention``) materializes
the [B, H, T, T] score tensor in HBM twice (softmax in, probabilities out) —
O(T^2) HBM traffic that dominates attention cost once T outgrows VMEM. This
kernel is the standard flash recipe on the MXU: stream K/V blocks through
VMEM against a resident Q block, maintain the online-softmax state (running
max, normalizer, weighted accumulator) in registers, and write only the
[T, D] output plus a [T] logsumexp. The backward pass recomputes
probabilities blockwise from the saved logsumexp (two kernels: dQ over query
blocks, dK/dV over key blocks) — nothing quadratic ever touches HBM.

Scope: per-device exact attention with key-padding masks (the shape the
transformer and the ring-attention local block need). The sequence axis
beyond one device is ring attention's job; this kernel is the fast local
block.

What each dtype gets (decided at trace time from the operands' dtype, no
flag): bf16 Q, K, V, dO reach the MXU as bf16, and the float32 tiles the
kernel computes (p, dS) are narrowed to bf16 just before their dots; float32
operands stay float32 at ``Precision.HIGHEST``; anything else is widened to
float32 first. Every dot accumulates in float32 (``preferred_element_type``),
and the scores, ``scale``, the online-softmax state (``m``, ``l``, ``corr``,
``lse``, ``delta``), ``exp`` and the accumulators are float32 whatever the
operands are: the v5e has no bf16 VPU/EUP. The state is lane-dense: Mosaic
keeps a ``[Bq, 1]`` value replicated across the 128 lanes, ``l`` is carried as
128 per-lane partial sums that cross the lanes once after the last block, and
dK/dV holds its score tile transposed so ``lse`` and ``delta`` meet it as
rows. The loop over the streamed axis is unrolled (``_block_loop``): on the
v5e its trip boundary, not a tile's arithmetic, set the pace.

What Mosaic accepts (compiled on a TPU v5e, jax 0.9.0 / libtpu 0.0.34; both
limits are checked in Python by ``_check_compilable`` so a request outside
them fails with the reason, not with a compiler dump):

- ``block_q`` / ``block_k`` are multiples of 128, or one block spans the
  whole padded sequence. The key-padding mask travels as ``[BH, 1, T]`` and
  is sliced along the 128-wide lane axis at ``block_k`` offsets (in dK/dV
  ``lse`` and ``delta`` likewise, at ``block_q`` offsets); 64 is not
  lane-aligned and is rejected. Compiled and checked against dense
  attention: 128, 256, 512, 128/256, and whole-sequence blocks.
- K/V (forward, dQ) and Q/dO (dK/dV) for one (batch, head) stay whole in
  VMEM, under Mosaic's 16 MiB scoped-VMEM limit. The largest pairs that
  compiled: T=16384 at D<=128 in bf16 and T=8192 at D<=128 in f32 (8 MiB
  per pair); f32 T=16384 D=128 (16 MiB) did not. Longer sequences are ring
  attention's job (``ring_flash_attention`` shards T first).

Two head widths (PR 31): q and k share the query/key width D, v has its own
Dv (``v.shape[-1]``), as latent attention has them (192 = 128 without
positions + 64 rotary, against 128). S = Q K^T, dQ and dK contract or
produce D lanes; P V, dP = dO V^T, dV, the output and dO are Dv wide. Each
is padded to its own multiple (192 -> 256 lanes, 128 stays), so nothing of
the value side pays for the wider key. ``scale`` (static, optional)
replaces ``1 / sqrt(D)``: YaRN's ``mscale`` enters there. With Dv == D and
no ``scale`` the calls trace to what they were before the second width.

On the CPU backend the kernels run in Pallas interpret mode so the CPU suite
exercises the same code path (house rule from kernels/dp_clip.py); interpret
mode takes any block size.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from fl4health_tpu.kernels._platform import interpret_default

_LANE = 128
NEG_INF = -1e30
# Whole-sequence operand pair (K+V, or Q+dO) a compiled kernel may keep in
# VMEM: the largest that compiled under the 16 MiB scoped limit (see the
# module docstring).
_VMEM_PAIR_BYTES = 8 * 1024 * 1024


def _check_compilable(block_q: int, block_k: int, tp: int, dp: int,
                      dvp: int, dtype) -> None:
    """Reject, with the reason, a request Mosaic cannot compile. ``dp`` is
    the padded query/key head width, ``dvp`` the padded value head width."""
    for name, block in (("block_q", block_q), ("block_k", block_k)):
        if block % _LANE != 0 and block != tp:
            raise ValueError(
                f"flash_attention: {name}={block} is not accepted by the "
                f"compiled (Mosaic) kernel — a block must be a multiple of "
                f"{_LANE} or span the whole padded sequence ({tp}): the "
                "key-padding mask is blocked and sliced along the 128-wide "
                "lane axis at block_k offsets, and only such sizes were "
                "compiled and checked on the chip. Other sizes run in "
                "interpret mode (CPU) only."
            )
    # K (query/key width) + V (value width) whole in the forward and dQ;
    # Q (query/key width) + dO (value width) whole in dK/dV: the same sum
    pair = (tp * (max(dp, _LANE) + max(dvp, _LANE))
            * jnp.dtype(dtype).itemsize)
    if pair > _VMEM_PAIR_BYTES:
        raise ValueError(
            f"flash_attention: sequence length {tp} (padded) at a query/key "
            f"head width of {dp} and a value head width of {dvp} (both "
            f"padded) in {jnp.dtype(dtype).name} keeps "
            f"{pair / 2**20:.1f} MiB of whole-sequence K (query/key width) "
            "and V (value width), and in dK/dV of Q and dO, per (batch, "
            f"head) in VMEM; the largest that compiles is "
            f"{_VMEM_PAIR_BYTES / 2**20:.0f} MiB (T=16384 bf16 / T=8192 f32 "
            "with both widths <= 128, under Mosaic's 16 MiB scoped-VMEM "
            "limit). Shard the sequence with ring_flash_attention or use "
            "dense attention."
        )


def _dot_precision(dtype) -> jax.lax.Precision:
    """f32 operands get faithful f32 dots; bf16 operands take the MXU's one
    native pass.

    Measured on TPU v5e (KERNELS r5): with the default precision Mosaic
    lowers an f32 dot to a single bf16 MXU pass, costing ~1.4e-3 abs error
    against the dense f32 attention the kernel must be a drop-in for.
    HIGHEST selects the multi-pass f32 algorithm for f32 operands only.
    bf16 operands are never widened: a widened operand at the default
    precision is narrowed back for the same single pass, so the cast buys no
    precision (PR 26: the same 1.78e-3 / 2.34e-3 worst error on out / dQ
    either way on the chip)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _mxu_operand(x: jax.Array) -> jax.Array:
    """bf16 and f32 reach the MXU as they arrive; anything else (f16, which
    Mosaic cannot load on a v5e: interpret mode only) is widened to f32."""
    return x if x.dtype in (jnp.bfloat16, jnp.float32) else x.astype(jnp.float32)


def _dot(a, b, contract, precision):
    """a . b over ``contract`` = (axis of a, axis of b), f32 accumulation.
    ``a`` is a float32 tile computed in the kernel (p, dS) or an operand; it
    takes ``b``'s dtype, so a bf16 block meets a bf16 tile."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def _pad_axis(x: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# Score tiles of 128 x 128 that one loop trip handles as straight-line code.
# Measured on the v5e (PR 26, bf16, D 64, blocks 128/128): the trip boundary,
# not the tile's arithmetic, set the pace. Forward at T 2,048, ms a call by
# blocks a trip: 40.3 (1), 23.5 (2), 20.4 (4), 14.3 (8), 10.6 (all 16, no loop
# left); at T 8,192: 151 (1), 33.3 (16), 27.2 (32), 23.3 (all 64). Nothing
# longer was measured, so longer axes loop over trips of 64.
_TILES_PER_TRIP = 64


def _block_loop(n_blocks: int, block: int, resident: int, step, carry,
                live=None):
    """``carry = step(pl.ds(j * block, block), carry)`` for j in
    range(n_blocks), each step a [resident, block] score tile (or its
    transpose). Consecutive blocks worth up to _TILES_PER_TRIP tiles of
    128 x 128 are one straight-line body, so the scheduler can start a
    block's first dot while the one before is still in its softmax; a longer
    axis loops over such trips and finishes with the remainder, so the code
    stays bounded at any length and any block size. ``live(start)`` (the
    causal kernels: is any score of the block at ``start`` on or under the
    diagonal) makes a block's step conditional; ``None`` traces the
    unconditional loop."""
    per_trip = max(1, _TILES_PER_TRIP
                   // (pl.cdiv(resident, _LANE) * pl.cdiv(block, _LANE)))

    def run(first, count, carry):
        for u in range(count):
            start = (first + u) * block
            if not isinstance(start, int):
                start = pl.multiple_of(start, block)
            ks = pl.ds(start, block)
            if live is None:
                carry = step(ks, carry)
            else:
                carry = jax.lax.cond(
                    live(start), lambda c, ks=ks: step(ks, c), lambda c: c,
                    carry)
        return carry

    if n_blocks <= per_trip:
        return run(0, n_blocks, carry)
    trips, rest = divmod(n_blocks, per_trip)
    carry = jax.lax.fori_loop(
        0, trips, lambda t, c: run(t * per_trip, per_trip, c), carry)
    return run(trips * per_trip, rest, carry)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _on_or_under_diagonal(q_start, nq: int, k_start, nk: int, transposed):
    """bool [nq, nk] (or its transpose): key position <= query position."""
    shape = (nk, nq) if transposed else (nq, nk)
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                              1 if transposed else 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                              0 if transposed else 1)
    return kpos <= qpos


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, block_k,
                scale, precision, causal):
    # Mosaic layout contract (learned on real silicon, KERNELS r5): every
    # block's trailing two dims must be (8k, 128k) or equal the array dims.
    # Row-per-(batch,head) vectors therefore travel as mask [BH, 1, Tp] and
    # lse/delta [BH, Tp, 1] (dK/dV takes them the other way round: see
    # _bwd_call), and all in-kernel state stays 2-D. Mosaic keeps a
    # [Bq, 1] value replicated along the lanes, so m and corr meet the score
    # tile without a broadcast; l is kept as 128 per-lane partial sums and
    # crosses the lanes once, after the last block.
    q = _mxu_operand(q_ref[0])  # [Bq, Dp]
    bq, dvp = q.shape[0], v_ref.shape[-1]
    lanes = _LANE if block_k % _LANE == 0 else block_k
    live = None
    if causal:
        q_start = pl.program_id(1) * bq
        live = lambda k_start: k_start <= q_start + (bq - 1)  # noqa: E731

    def step(ks, carry):
        m, l, acc = carry  # m: [Bq, 1], l: [Bq, lanes], acc: [Bq, Dvp], f32
        kb = _mxu_operand(k_ref[0, ks, :])
        vb = _mxu_operand(v_ref[0, ks, :])
        keep = mask_ref[0, :, ks] > 0  # [1, Bk]
        if causal:
            keep = keep & _on_or_under_diagonal(q_start, bq, ks.start,
                                                block_k, False)
        s = _dot(q, kb, (1, 1), precision) * scale  # [Bq, Bk]
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p[:, :lanes]
        for c in range(lanes, block_k, lanes):
            l = l + p[:, c:c + lanes]
        acc = acc * corr + _dot(p, vb, (1, 0), precision)
        return m_new, l, acc

    m, l, acc = _block_loop(
        k_ref.shape[1] // block_k, block_k, bq, step,
        (jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, lanes), jnp.float32),
         jnp.zeros((bq, dvp), jnp.float32)), live)
    denom = jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-20)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(denom)  # [Bq, 1]


def _fwd_call(q, k, v, mask, block_q, block_k, scale, interpret, causal):
    bh, tp, dp = q.shape
    dvp = v.shape[-1]  # the value head width: v and the output
    grid = (bh, tp // block_q)
    kernel = functools.partial(_fwd_kernel, block_k=block_k, scale=scale,
                               precision=_dot_precision(q.dtype),
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tp, dvp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dvp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, dvp), q.dtype),
            jax.ShapeDtypeStruct((bh, tp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, mask)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_k, scale, precision, causal):
    q = _mxu_operand(q_ref[0])
    do = _mxu_operand(do_ref[0])
    lse = lse_ref[0]  # [Bq, 1]
    delta = delta_ref[0]  # [Bq, 1] = rowsum(dO * O)
    bq = q.shape[0]
    live = None
    if causal:
        q_start = pl.program_id(1) * bq
        live = lambda k_start: k_start <= q_start + (bq - 1)  # noqa: E731

    def step(ks, dq):
        kb = _mxu_operand(k_ref[0, ks, :])
        vb = _mxu_operand(v_ref[0, ks, :])
        keep = mask_ref[0, :, ks] > 0  # [1, Bk]
        if causal:
            keep = keep & _on_or_under_diagonal(q_start, bq, ks.start,
                                                block_k, False)
        s = _dot(q, kb, (1, 1), precision) * scale
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = _dot(do, vb, (1, 1), precision)
        # dS = p * (dP - delta) * scale; the scale waits for the sum
        return dq + _dot(p * (dp - delta), kb, (1, 0), precision)

    dq = _block_loop(k_ref.shape[1] // block_k, block_k, bq, step,
                     jnp.zeros(q.shape, jnp.float32), live)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q, scale, precision, causal):
    # The score tile is held transposed, [Bk, Bq]: dV += P^T dO and
    # dK += dS^T Q are then plain row-major dots, and lse / delta meet the
    # tile as rows [1, Bq] (a sublane broadcast) where the [Bq, Bk] form
    # paid a [128, 128] transpose and 32 lane-broadcast permutes a tile
    # (18.4 -> 14.3 ms a call on the v5e, PR 26).
    kb = _mxu_operand(k_ref[0])  # [Bk, Dp]
    vb = _mxu_operand(v_ref[0])
    bk = kb.shape[0]
    live = None
    if causal:
        k_start = pl.program_id(1) * bk
        live = lambda q_start: q_start + (block_q - 1) >= k_start  # noqa: E731

    def step(qs, carry):
        dk, dv = carry
        q = _mxu_operand(q_ref[0, qs, :])
        do = _mxu_operand(do_ref[0, qs, :])
        lse = lse_ref[0, :, qs]  # [1, Bq]
        delta = delta_ref[0, :, qs]
        pt = jnp.exp(_dot(kb, q, (1, 1), precision) * scale - lse)
        if causal:
            pt = jnp.where(_on_or_under_diagonal(qs.start, block_q, k_start,
                                                 bk, True), pt, 0.0)
        dpt = _dot(vb, do, (1, 1), precision)
        dv = dv + _dot(pt, do, (1, 0), precision)
        dk = dk + _dot(pt * (dpt - delta), q, (1, 0), precision)
        return dk, dv

    dk0 = jnp.zeros(kb.shape, jnp.float32)
    dv0 = dk0 if vb.shape == kb.shape else jnp.zeros(vb.shape, jnp.float32)
    dk, dv = _block_loop(q_ref.shape[1] // block_q, block_q, bk,
                         step, (dk0, dv0), live)
    # A row of dK / dV depends on its own key alone, so the key-padding mask
    # is one select on the sums: a padded key's row is zero, as when every
    # p of that key was zeroed in the loop.
    keep = keep_ref[0] > 0  # [Bk, 1]
    dk_ref[0] = jnp.where(keep, dk * scale, 0.0).astype(dk_ref.dtype)
    dv_ref[0] = jnp.where(keep, dv, 0.0).astype(dv_ref.dtype)


def _bwd_call(q, k, v, mask, o, lse, do, block_q, block_k, scale, interpret,
              dlse, causal):
    bh, tp, dp = q.shape
    dvp = v.shape[-1]  # the value head width: v, o, do and dv
    # lse is a differentiable OUTPUT (ring-flash merge): its cotangent
    # enters the score gradient as dS = p*(dP - delta + dlse), i.e. the
    # delta slot carries (delta - dlse) — kernels unchanged. Plain
    # flash_attention reaches here with dlse = zeros (custom_vjp
    # instantiates the dropped output's cotangent).
    delta = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                     keepdims=True)
             - dlse.astype(jnp.float32))  # [BH, Tp, 1]

    prec = _dot_precision(q.dtype)
    dq_kernel = functools.partial(_bwd_dq_kernel, block_k=block_k, scale=scale,
                                  precision=prec, causal=causal)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tp, dvp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, dvp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tp, dp), q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, mask, do, lse, delta)

    # dK/dV sees queries along the lanes: lse and delta as rows [BH, 1, Tp]
    # (sliced at block_q offsets like the forward's mask), the key mask as a
    # column [BH, Tp, 1]. Same seven operands.
    dkv_kernel = functools.partial(_bwd_dkv_kernel, block_q=block_q,
                                   scale=scale, precision=prec, causal=causal)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, tp // block_k),
        in_specs=[
            pl.BlockSpec((1, tp, dp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dvp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, tp, dvp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dvp), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, dp), k.dtype),
            jax.ShapeDtypeStruct((bh, tp, dvp), v.dtype),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, mask.reshape(bh, tp, 1), do, lse.reshape(bh, 1, tp),
      delta.reshape(bh, 1, tp))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp over padded [BH, Tp, Dp] internals
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_padded_lse(q, k, v, mask, block_q, block_k, scale, interpret,
                      causal):
    """(out, lse) pair with lse a first-class differentiable output so
    partial-attention results can be merged exactly (ring-flash). The
    plain-``out`` path (flash_attention) wraps this and drops lse — its
    zero cotangent makes _bwd_call's dlse term vanish, so ONE custom_vjp
    serves both APIs."""
    return _fwd_call(q, k, v, mask, block_q, block_k, scale, interpret,
                     causal)


def _flash_padded_lse_fwd(q, k, v, mask, block_q, block_k, scale, interpret,
                          causal):
    out, lse = _fwd_call(q, k, v, mask, block_q, block_k, scale, interpret,
                         causal)
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_padded_lse_bwd(block_q, block_k, scale, interpret, causal, res,
                          cts):
    do, dlse = cts
    q, k, v, mask, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, mask, out, lse, do, block_q, block_k,
                           scale, interpret, dlse=dlse, causal=causal)
    return dq, dk, dv, None


_flash_padded_lse.defvjp(_flash_padded_lse_fwd, _flash_padded_lse_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pad_mask: jax.Array | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Exact softmax attention, flash-style. q, k: [B, T, H, D], v:
    [B, T, H, Dv]; pad_mask: [B, T] with 1 = real token (key positions);
    returns [B, T, H, Dv]. Drop-in for ring_attention._dense_attention.

    The value head width ``Dv`` is ``v``'s own and may differ from the
    query/key width ``D`` (latent attention: 192 and 128): S = Q K^T runs at
    D, P V, dV and dP at Dv, each padded to its own lane multiple. ``scale``
    (static) multiplies the scores; ``None`` is ``1 / sqrt(D)``.

    ``causal`` (static): a query sees the keys at its own position and
    before; key blocks wholly above the diagonal are skipped in all three
    kernels. ``k`` / ``v`` with a single head (``[B, T, 1, D]``) are shared
    by every query head (multi-query attention): the wrapper broadcasts
    them, and their gradient is the sum over the query heads.

    pad_mask is NON-differentiable: it is a binary padding indicator, and the
    custom VJP returns a zero cotangent for it (a soft/learned mask would get
    silent zero grads here — use the dense path for that; stop_gradient in
    the shared prep makes the contract explicit)."""
    out, _ = flash_attention_lse(q, k, v, pad_mask, block_q, block_k,
                                 interpret, causal, scale)
    return out


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pad_mask: jax.Array | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """flash_attention returning (out [B,T,H,Dv], lse [B,H,T]) with lse a
    DIFFERENTIABLE output — the partial-softmax statistic that lets two
    attention results over disjoint key sets merge exactly:
    ``L = logsumexp_j(lse_j); out = sum_j exp(lse_j - L) * out_j``. This is
    the local block of ring-flash attention (parallel/ring_attention.py
    ``ring_flash_attention``). Query rows with no valid key anywhere get
    lse ~ NEG_INF + log(1e-20) — a large FINITE negative, deliberately not
    -inf: the ring merge computes exp(lse - M) and a true -inf would turn
    all-padded rows into inf-inf = NaN. Their merge weight underflows to 0
    either way; fully-padded rows' out is garbage, exactly like
    flash_attention."""
    if interpret is None:
        interpret = interpret_default()
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if k.shape[2] == 1 and h > 1:
        k = jnp.broadcast_to(k, q.shape)
        v = jnp.broadcast_to(v, (*q.shape[:3], dv))
    if pad_mask is None:
        pad_mask = jnp.ones((b, t), jnp.float32)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # [B,T,H,D] -> [B*H, T, D]; pad T to the block grid and each head width
    # (query/key D, value Dv) per d_multiple below (64 for a width <= 64,
    # else the 128 lane width: a padded width is NOT guaranteed to be a
    # multiple of 128).
    # T must divide by BOTH block sizes (the q grid tiles by block_q while
    # each kernel loops T/block_k key blocks) — lcm, not max: padding only to
    # max(block_q, block_k) would silently drop trailing key blocks for
    # non-dividing pairs like 48/32.
    t_multiple = math.lcm(block_q, block_k)

    # Width padding: blocks always span a full head width (query/key or
    # value), and a block dim equal to the array dim is legal on Mosaic
    # whatever its size — so pad only to the sublane-packable 64 for the
    # ubiquitous width<=64 case instead of burning 2x FLOPs/VMEM traffic on
    # 128-lane zero padding (the r5 long-context config is exactly 64).
    def to_bh(x):
        width = x.shape[-1]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, width)
        return _pad_axis(_pad_axis(x, 2, 64 if width <= 64 else _LANE), 1,
                         t_multiple)

    qp, kp, vp = to_bh(q), to_bh(k), to_bh(v)
    if not interpret:
        _check_compilable(block_q, block_k, qp.shape[1], qp.shape[2],
                          vp.shape[2], q.dtype)
    pad_mask = jax.lax.stop_gradient(pad_mask)
    maskp = _pad_axis(pad_mask.astype(jnp.float32), 1, t_multiple)
    # [BH, 1, Tp]: keys-per-row as the trailing (lane) dim — see _fwd_kernel's
    # Mosaic layout note
    maskp = jnp.repeat(maskp, h, axis=0)[:, None, :]

    def padded(qp, kp, vp, maskp):
        return _flash_padded_lse(qp, kp, vp, maskp, block_q, block_k, scale,
                                 interpret, causal)

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.are_all_axes_auto:
        # Inside a program traced for a mesh (parallel/program.py): XLA
        # cannot auto-partition a Mosaic custom call ("Mosaic kernels cannot
        # be automatically partitioned"), so the call goes manual. The specs
        # say "replicated" for these per-client operands; the engine's vmap
        # over clients (spmd_axis_name="clients") turns its batched axis
        # into P("clients"), so each chip runs the kernel on its own clients
        # only. Already-manual contexts (ring attention's shard_map body)
        # call the kernel directly.
        padded = jax.shard_map(padded, mesh=mesh, in_specs=(P(),) * 4,
                               out_specs=(P(), P()), check_vma=False)
    out, lse = padded(qp, kp, vp, maskp)
    out = out[:, :t, :dv].reshape(b, h, t, dv)
    lse = lse[:, :t, 0].reshape(b, h, t)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype), lse
