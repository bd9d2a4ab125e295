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
  whole padded sequence. The key-padding mask travels as ``[BH, 1, T]``
  (``[B, 1, T]`` lane-indexed) and is sliced along the 128-wide lane axis
  at ``block_k`` offsets (in dK/dV ``lse`` and ``delta`` likewise, at
  ``block_q`` offsets); 64 is not lane-aligned and is rejected. Compiled and checked against dense
  attention: 128, 256, 512, 128/256, and whole-sequence blocks.
- K/V (forward, dQ) and Q/dO (dK/dV) for one (batch, head) stay whole in
  VMEM (every part of K, or of Q, counted at its lane-padded width), under
  Mosaic's 16 MiB scoped-VMEM limit. The largest pairs that compiled: T=16384 at D<=128 in bf16 and T=8192 at D<=128 in f32 (8 MiB
  per pair); f32 T=16384 D=128 (16 MiB) did not. Longer sequences are ring
  attention's job (``ring_flash_attention`` shards T first).

Two head widths: q and k share the query/key width D, v has its own Dv
(``v.shape[-1]``), as latent attention has them (192 = 128 without positions
+ 64 rotary, against 128). S = Q K^T, dQ and dK contract or produce D lanes;
P V, dP = dO V^T, dV, the output and dO are Dv wide. ``scale`` (static,
optional) replaces ``1 / sqrt(D)``: YaRN's ``mscale`` enters there.

The score as a sum of parts: ``q`` and ``k`` may be tuples of parts, ``S =
sum_i q_i k_i^T``, each part's dot at its own width, added in float32 before
the scale; dQ and dK come back per part. Latent attention passes ``(q_nope
[B,T,H,128], q_pe [B,T,H,64])`` and ``(k_nope [B,T,H,128], k_pe
[B,T,1,64])``: nothing is concatenated to 192 lanes or padded to 256.

Two paths, chosen by the operands' shapes at trace time (``_lane_kinds``; no
knob, the results are the same):

- **lane-indexed**: every operand is addressed where the model holds it, by
  a ``BlockSpec`` index map, and the head is a grid axis. ``[B, T, H, w]`` is
  viewed as ``[B, T, H*w]`` (free) and head h is lane block h (``lane``: w a
  multiple of 128); a part of k, or k and v, with ONE head under several
  query heads is ``[B, T, w]`` at block ``(b, ., 0)`` for every head (``row``:
  any w, the block spans the whole last axis; no ``broadcast_to``), and its
  gradient is summed over the heads in float32 INSIDE the dK/dV call (the
  head axis innermost, the output block resident across it: ``_store``); the
  key-padding mask is ``[B, 1, Tp]`` at ``(b, 0, 0)``, not repeated per head.
  **Grouped** key/value heads (grouped-query attention: ``Hkv`` heads of a
  multiple of 128 lanes under ``H = rep * Hkv`` query heads) are the same
  ``[B, T, Hkv*w]`` view with query head h reading lane block ``h // rep``
  (``grouped``; no ``repeat``), and dK / dV sum a group's ``rep`` heads in
  float32 inside the dK/dV call as the shared part sums all of them: the
  head axis innermost, the block resident across the ``rep`` consecutive
  steps that map to it.
  The output is ``[B, T, H*Dv]``, which an output projection takes as it
  is: no transpose before the call, none after, no pad of a width. The one
  copy left is of a part of q narrower than 128 lanes over a shared part of k
  (the rotary 64): it is laid out ``[B, H, T, w]`` (``head``), and its
  gradient comes back so.
  **Packed** (PR 40): heads of 64 (32) lanes ride g = 2 (4) to a lane block
  of the same ``[B, T, H*w]`` view. The block is ``(1, n, 128)`` at lane
  block ``h // g``, the grid's head axis walks the H / g groups, and a grid
  step runs the kernel's body once a head over the SAME 128-lane tiles, the
  heads told apart by a lane mask (``_head_lanes``), not by cutting 64-lane
  halves out of a tile: ``S_j = (q . lanes_j) k^T`` contracts all 128 lanes
  (the other heads' lanes of q are zero, so the sum is ``q_j k_j^T`` plus
  exact zeros; q is masked once a tile, outside the loop over key blocks; a
  64-deep and a 128-deep contraction cost the 128 x 128 MXU the same pass,
  so this is the transposed path's pass count); ``P_j v`` is ``[Bq, 128]``
  with ``P_j v_j`` in lanes_j, and the g results are merged by lane
  (``_merge``) into ONE float32 accumulator with a per-lane rescale and
  leave as one lane-dense block; ``dS_j k`` likewise; in dK/dV k and v are
  masked once a key block and ``dS_j^T q``, ``P_j^T dO`` merged the same
  way. The statistics (``m``, ``l``, ``lse``, ``delta``) stay per head,
  ``[B, H, Tp, 1]`` with g of them a block; the key mask is the group's.
  Outside the kernels delta = rowsum(dO O) a head is a product of the
  float32 ``dO * O`` with the heads' 0/1 membership matrix (``_bwd_call``:
  64 lanes are no lane tile, so a reshape-and-reduce made XLA lay the
  product out T-minor first).
  Where a step holds one head (g = 1: the other kinds) ``_head_lanes`` is
  ``[None]`` and nothing of this is traced: the other calls lower to the text
  they did.
- **transposed**: every operand copied to ``[B*H, Tp, Dpadded]`` (each width
  padded to its own multiple: 64 for a width <= 64, else 128), one grid row
  per (batch, head), a shared head broadcast first, parts concatenated first.
  Any call whose shapes the rule below leaves out; with Dv == D, one part and
  no ``scale`` it traces to what it was before the second width and before
  the parts (``test_equal_widths_and_no_scale_trace_to_the_parents_
  program``).

The rule: packed when every part of q / k and v is 64, or is 32, lanes wide
with heads of its own, as many on each, a multiple of g. Else lane-indexed
when v's width is a multiple of 128 and every part of q / k is a multiple of
128 wide with heads of its own, or is a shared one-head part of k (its q part
may then be narrow, as above). So the encoder's 12 heads of 64, latent
attention at the published widths and 20 heads of 128 over one key/value head
take it, and so do 32 query heads over 2 key/value heads of 128 (grouped);
an odd count of narrow heads, 80 or 96 lanes, 16 and under (8 bodies a step
and more: never built), a narrow v beside wider q / k, or a narrow part with
FEWER key heads than query heads (repeated to the query heads first) does
not. ``count_call_sites`` counts
the traced calls by path for a build-time gauge.

What Mosaic accepted and refused for narrow heads (compiled for a described
v5e, jax 0.9.0): a ``(1, block_q, 64)`` block of ``[B, T, H*64]`` is REFUSED
by the Pallas TPU lowering (a block's last two dims must divide by 8 and 128
or equal the array's); a ``(1, block_q, 128)`` block holding two heads' lanes
compiles, and so do the packed kernels at 4 clients x 8 x 2,048 x 12 x 64 and
at 32 lanes, bf16 and f32, causal and not, blocks 128/128: a ``jnp.where`` of
a bf16 tile under an iota lane mask, the ``(None, g, block_q, 1)`` and
``(None, g, 1, Tp)`` blocks of the per-head statistics
(``tests/kernels/test_flash_v5e_compile.py``). For the 64-lane ROTARY part of
q over a shared key (latent attention) packing would make the no-position
part's head axis step by two; there ``[B, H, T, 64]`` with a ``(None, 1,
block_q, 64)`` block (the last dim equals the array's) is the form taken: the
transpose is a third of q's bytes and XLA fuses it into the rotary fusion
that computes the part anyway.

A sliding window (``window``, static, beside ``causal``; PR 43): query i sees
key j iff ``0 <= i - j < window`` (its own position and the ``window - 1``
before it) and j is no pad. A tile wholly behind the window is not executed,
as a tile wholly above the diagonal is not, so a call does work in
proportion to the band. ``live_tiles`` counts the tiles left, on Python
integers: 70 of the causal 136 at T 8,192, a window of 2,048 and blocks of
512. K / V of a key head (Q / dO in dK/dV) still lie whole in VMEM, so the
window spares no fetch and lifts no limit of ``_check_compilable``; the index
maps, the grids and both paths are what they were.

How a causal call walks the tiles it executes (PR 44). The key blocks a query
block executes (in dK/dV, the query blocks of a key block) are ONE range of
the streamed axis, whose ends are integers of the grid index, the blocks and
the window (``_live_range``), and inside it only the tiles that the diagonal
or the window's far edge crosses can be changed by a positional mask: the
first and the last run of the range; the run between them is wholly visible
and takes the key-padding mask alone. The kernels walk that range
(``_walk_live``), ascending as before: where most resident blocks agree on
the three runs' lengths (a sliding layer past its first window: one far-edge
tile, three interior, the diagonal's, at blocks of 512 under 2,048), one
scalar condition picks ONE straight-line body of that many steps from a
dynamic start; elsewhere a run whose length the grid index sets goes as
whole straight-line trips under a trip count read from it, with what is
short of a trip before them as its binary digits, each a straight-line body
behind one condition (``_steps``). Until PR 44 every block step of the whole
axis sat behind a scalar condition of its own and every executed tile built
the positional mask: at T 8,192 and 512 / 512 the forward ran 2.0 (causal)
and 3.1 (window) microseconds an executed tile where the straight-line body
of a call that is not causal runs 0.81, and 1.02 / 1.09 after (v5e, bf16, 32
heads of 128 over 4; dQ 1.38 / 1.59 -> 1.17 / 1.24, dK/dV 2.17 / 2.97 ->
1.61 / 1.78). The same tiles execute, in the same order, with the same masks
wherever a mask can change a score. ``traversal`` counts edge and interior
tiles and the block steps still behind a condition from the same function,
for ``count_call_sites`` and the models' build gauges. A causal call asks
Mosaic for a scoped-VMEM limit of 32 MiB (``_CAUSAL_PARAMS``); a call that is
not causal traces to the program it always traced to
(``tests/kernels/test_flash_window.py``, ``test_flash_causal.py``).

Under a rematerialised layer: the forward rule of the ``custom_vjp`` gives
``out`` and ``lse`` names (``SAVED_NAMES``, through ``core/remat.py``), so a
``jax.checkpoint`` whose policy keeps those names saves the two arrays the
backward reads of the forward and the layer's recompute holds no ``flash_fwd``
(per byte kept the dearest thing a layer recomputes: 4 T T D FLOPs for T D
2 bytes). ``lse`` is kept as ``[.., Tp]``, not as the ``[.., Tp, 1]`` the
kernel writes: a trailing axis of 1 is padded 128-fold in HBM. Where no
policy asks for the names they lower to nothing.

On the CPU backend the kernels run in Pallas interpret mode so the CPU suite
exercises the same code path (house rule from kernels/dp_clip.py); interpret
mode takes any block size.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from fl4health_tpu.core.remat import named
from fl4health_tpu.kernels._platform import interpret_default

_LANE = 128
# Head widths that ride 128 // w to a lane block (``_lane_kinds``): the two
# that were built and compiled for the v5e (64 also measured there). A
# kernel's body runs once a head of its step, so 16 lanes and under (8 heads
# a step and more) stay transposed.
_PACKED_WIDTHS = (64, 32)
# What the backward reads of the forward, under the names a remat policy may
# keep (core/remat.py): ``out``, and ``lse`` without its trailing axis of 1.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"
SAVED_NAMES = (FLASH_OUT, FLASH_LSE)
NEG_INF = -1e30
# A causal call's scoped-VMEM limit. The walk of the live range holds two
# forms of the loop (``_walk_live``), and their temporaries beside the
# double-buffered whole-sequence pair passed Mosaic's default 16 MiB by
# 160 kB in dQ at the trinity_mini cell's shape (compiled for a described
# v5e, PR 44); a call that is not causal passes no parameters, as before.
_CAUSAL_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20)
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


def _per_trip(block: int, resident: int) -> int:
    """Blocks of the streamed axis to a straight-line trip."""
    return max(1, _TILES_PER_TRIP
               // (pl.cdiv(resident, _LANE) * pl.cdiv(block, _LANE)))


def _steps(first, count, block: int, per_trip: int, step, carry,
           at_most: int | None = None):
    """``carry = step(pl.ds(j * block, block), carry)`` for j in
    range(first, first + count), ascending. ``count`` a Python integer:
    consecutive blocks worth up to a trip are one straight-line body, a
    longer run loops over such trips and finishes with the remainder, so the
    code stays bounded at any length. ``count`` a scalar of the grid index,
    no larger than ``at_most``: the ``count % per_trip`` blocks short of a
    trip go first, as the binary digits of that number, each digit a
    straight-line body behind one scalar condition (``_chunks``), then whole
    straight-line trips under a trip count read from the scalar: the run
    ends where ``first + count`` says, no trip without a live block is
    entered and no block step has a condition of its own."""
    def run(first, count, carry):
        for u in range(count):
            start = (first + u) * block
            if not isinstance(start, int):
                start = pl.multiple_of(start, block)
            carry = step(pl.ds(start, block), carry)
        return carry

    if isinstance(count, int):
        if count <= per_trip:
            return run(first, count, carry)
        trips, rest = divmod(count, per_trip)
        carry = jax.lax.fori_loop(
            0, trips, lambda t, c: run(first + t * per_trip, per_trip, c),
            carry)
        return run(first + trips * per_trip, rest, carry)
    whole_trips = at_most >= per_trip
    rest = jax.lax.rem(count, per_trip) if whole_trips else count
    for size in _chunks(min(per_trip - 1, at_most)):
        taken = rest & size  # 0 or ``size``
        carry = jax.lax.cond(
            taken != 0, lambda c, first=first, size=size: run(first, size, c),
            lambda c: c, carry)
        first = first + taken
    if whole_trips:
        carry = jax.lax.fori_loop(
            0, jax.lax.div(count, per_trip),
            lambda t, c: run(first + t * per_trip, per_trip, c), carry)
    return carry


def _chunks(most: int) -> list:
    """The powers of two, largest first, whose sums give every count up to
    ``most``: a run of unknown length under a trip is walked as its binary
    digits, each a straight-line body behind one scalar condition."""
    return [1 << k for k in reversed(range(most.bit_length()))]


def _live_range(start, resident: int, block: int, n_blocks: int,
                window: int | None, keys_streamed: bool):
    """The streamed blocks that the resident block at position ``start``
    executes under ``causal`` (and a ``window``) are one range, ``[a, e)``,
    in three runs: ``[a, b)`` and ``[c, e)`` hold tiles that a positional
    mask can change (the diagonal crosses them, or the window's far edge),
    ``[b, c)`` tiles wholly under the diagonal and wholly inside the window,
    which take no positional mask. ``keys_streamed``: the forward and dQ (a
    query block resident, key blocks streamed: the window's edge first, the
    diagonal last); else dK/dV (a key block resident, query blocks streamed:
    the diagonal first). Integer arithmetic that holds for a Python integer
    and for a scalar of the grid index alike (every dividend is >= 0), so
    the kernels and the counts (``traversal``) ask one function."""
    def div(x, n):
        return x // n if isinstance(x, int) else jax.lax.div(x, n)

    def clamp(x, lo, hi):
        if all(isinstance(y, int) for y in (x, lo, hi)):
            return max(lo, min(x, hi))
        return jnp.clip(x, lo, hi)

    if keys_streamed:  # keys [j * block, (j + 1) * block) against queries
        q_start, bq, bk = start, resident, block
        e = div(q_start + (bq - 1), bk) + 1  # one past the diagonal's block
        c = div(q_start + 1, bk)  # first not wholly on or under the diagonal
        if window is None:
            a = b = 0
        else:
            # the block of the FIRST query's oldest key, and the first block
            # wholly inside the LAST query's window
            a = div(clamp(q_start - (window - 1), 0, q_start), bk)
            b = div(clamp(q_start + bq - window, 0, q_start + bq) + (bk - 1),
                    bk)
    else:  # queries [i * block, (i + 1) * block) against the key block
        k_start, bk, bq = start, resident, block
        a = div(k_start, bq)  # the first block with a query at or past it
        # one past the last block with a query BEFORE the block's last key
        b = div(k_start + bk + bq - 2, bq)
        if window is None:
            c = e = n_blocks
        else:
            c = div(k_start + window, bq)  # first not wholly in the window
            e = div(k_start + bk + window - 2, bq) + 1  # the edge's last
            e = clamp(e, 0, n_blocks)
    b = clamp(b, a, e)
    return a, b, clamp(c, b, e), e


def _walk_plan(n_resident: int, resident: int, block: int, n_blocks: int,
               window: int | None, keys_streamed: bool):
    """How the causal kernels walk their ranges, from Python integers: the
    three runs' lengths (``_live_range``) of every resident block; the
    lengths that most resident blocks share, if more than one does
    (``steady``: a sliding layer's ``(1, 3, 1)`` past the first window), else
    None; and per run the one length all share (a Python integer: the run is
    straight-line in every resident block) or None with the largest."""
    counts = []
    for p in range(n_resident):
        a, b, c, e = _live_range(p * resident, resident, block, n_blocks,
                                 window, keys_streamed)
        counts.append((b - a, c - b, e - c))
    steady, shared = collections.Counter(counts).most_common(1)[0]
    runs = [(lens[0] if len(set(lens)) == 1 else None, max(lens))
            for lens in zip(*counts)]
    if shared == 1 or shared == n_resident:  # none to share, or all static
        steady = None
    return counts, steady, runs


def _walk_live(pid, n_resident: int, resident: int, block: int,
               n_blocks: int, window: int | None, keys_streamed: bool, step,
               carry):
    """The causal kernels' loop: ``carry = step(pl.ds(j * block, block),
    carry, edge)`` over the live range of resident block ``pid`` (a scalar
    of the grid index), ascending; ``edge`` (static) says whether the tile
    needs the positional mask. Where the runs' lengths are those most
    resident blocks share, the whole range is ONE straight-line body from a
    dynamic start (one scalar condition a resident block picks it); else
    each run goes by its own length: straight-line where every resident
    block agrees on it, else ``_steps``' whole trips with the binary digits
    of what is short of a trip before them. No block that is not live is
    stepped over, and none that is live is left out."""
    per_trip = _per_trip(block, resident)
    _, steady, runs = _walk_plan(n_resident, resident, block, n_blocks,
                                 window, keys_streamed)
    a, b, c, e = _live_range(pid * resident, resident, block, n_blocks,
                             window, keys_streamed)
    firsts, lengths = (a, b, c), (b - a, c - b, e - c)

    def walk(lengths, at_most):
        def go(carry):
            for first, n, most, edge in zip(firsts, lengths, at_most,
                                            (True, False, True)):
                carry = _steps(
                    first, n, block, per_trip,
                    lambda ks, c_, edge=edge: step(ks, c_, edge), carry, most)
            return carry
        return go

    general = walk([n if n is not None else dyn
                    for (n, _), dyn in zip(runs, lengths)],
                   [most for _, most in runs])
    if steady is None:
        return general(carry)
    is_steady = functools.reduce(
        jnp.logical_and, [dyn == n for dyn, n in zip(lengths, steady)])
    return jax.lax.cond(is_steady, walk(steady, steady), general, carry)


def _block_loop(causal: bool, axis: int, resident: int, block: int, t: int,
                window: int | None, keys_streamed: bool, step, carry):
    """A kernel's loop over the streamed axis of ``t`` positions, each step
    a [resident, block] score tile (or its transpose): every block where the
    call is not causal, consecutive blocks worth up to _TILES_PER_TRIP tiles
    of 128 x 128 as one straight-line body, so the scheduler can start a
    block's first dot while the one before is still in its softmax
    (``_steps``); else the live range of the resident block that grid axis
    ``axis`` names (``_walk_live``)."""
    if not causal:
        return _steps(0, t // block, block, _per_trip(block, resident),
                      lambda ks, c: step(ks, c, False), carry)
    return _walk_live(pl.program_id(axis), t // resident, resident, block,
                      t // block, window, keys_streamed, step, carry)


def traversal(t: int, block_q: int, block_k: int,
              window: int | None = None) -> dict:
    """What a head of a causal call over ``t`` (padded) positions executes
    in its forward (dQ walks the same ranges), from the kernels' own
    ``_live_range`` on Python integers: ``tiles_edge``, which build the
    positional mask, ``tiles_interior``, which take the key-padding mask
    alone (together ``live_tiles``), and ``cond_steps``: block steps that
    sit behind a scalar condition of their own, summed over the query blocks
    whose path holds them (none in a query block that walks the steady
    straight-line body)."""
    n_q, n_k = t // block_q, t // block_k
    counts, steady, runs = _walk_plan(n_q, block_q, block_k, n_k, window,
                                      True)
    per_trip = _per_trip(block_k, block_q)
    conds = sum(sum(_chunks(min(per_trip - 1, most)))
                for n, most in runs if n is None)
    return {"tiles_edge": sum(n[0] + n[2] for n in counts),
            "tiles_interior": sum(n[1] for n in counts),
            "cond_steps": conds * sum(1 for n in counts if n != steady)}


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _on_or_under_diagonal(q_start, nq: int, k_start, nk: int, transposed,
                          window=None):
    """bool [nq, nk] (or its transpose): key position <= query position,
    and under a ``window`` the query's own position and the ``window - 1``
    before it only (``query - key < window``)."""
    shape = (nk, nq) if transposed else (nq, nk)
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                              1 if transposed else 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                              0 if transposed else 1)
    seen = kpos <= qpos
    if window is not None:
        seen = seen & (qpos - kpos < window)
    return seen


def _reaches_window(q_start, k_start, block_k: int, window: int):
    """Is the LAST key of the key block at ``k_start`` inside the window of
    the FIRST query of the query block at ``q_start``: if not, every score
    of the tile lies behind the window and the tile is not executed (all
    three kernels ask this of a tile, and ``live_tiles`` counts by it)."""
    return k_start + (block_k - 1) > q_start - window


def live_tiles(t: int, block_q: int, block_k: int,
               window: int | None = None) -> int:
    """Score tiles of ``block_q x block_k`` that a causal call over ``t``
    (padded) positions executes a head, in each of its three kernels: those
    with a score on or under the diagonal and, under a ``window``, inside
    it (the kernels' own two conditions, on Python integers)."""
    return sum(
        1 for q_start in range(0, t, block_q) for k_start in range(0, t, block_k)
        if k_start <= q_start + (block_q - 1) and (
            window is None
            or _reaches_window(q_start, k_start, block_k, window)))


def _layout_of(kinds, qs, v, mask):
    """Where the operands lie and how the grid walks them. The transposed
    path (``kinds`` None): every operand ``[B*H, Tp, w]``, one grid row per
    (batch, head). The lane-indexed path: ``kinds = (q kinds, k kinds, v
    kind, head steps, group)``, each operand addressed where the model holds
    it (``_spec``), the head a grid axis of its own whose step holds
    ``group`` heads (more than one: narrow heads packed side by side in a
    128-lane block), ``rep`` query heads to a key/value head of a ``grouped``
    operand. Returns the kinds, the head steps, the group, rep, B (or B*H),
    Tp, each part's and v's width a head step, and the kinds of the output
    and of the per-row statistics."""
    q_kinds, k_kinds, v_kind, heads, group, rep = kinds or (
        ("row",), ("row",), "row", None, 1, 1)

    def width(x, kind):
        return x.shape[-1] // _lane_blocks(kind, heads, rep)

    out, vec = ("row", "row") if heads is None else ("lane", "head")
    return (q_kinds, k_kinds, v_kind, heads, group, rep, mask.shape[0],
            mask.shape[-1], [width(x, kind) for x, kind in zip(qs, q_kinds)],
            width(v, v_kind), out, vec)


def _lane_blocks(kind, heads, rep):
    """How many head blocks an operand's last axis holds: ``heads`` of its
    own (``lane``), one for every ``rep`` query heads (``grouped``), else
    one."""
    if kind == "lane":
        return heads
    return heads // rep if kind == "grouped" else 1


def _spec(kind, n, width, pos, whole=False, group=1, rep=1):
    """BlockSpec of ``n`` positions (``whole``: the one block that is all of
    them) of one head step of one operand. ``pos(*grid) -> (batch, head step,
    block)``. ``row``: ``[B, Tp, w]``, no head axis (the transposed path's
    ``[B*H, Tp, w]``, and a part every head shares); ``lane``: ``[B, Tp,
    H*w]``, the head step a lane block (one head a multiple of 128 lanes
    wide, or the ``group`` narrow heads that fill 128); ``grouped``: ``[B,
    Tp, (H / rep)*w]``, a key/value head that ``rep`` consecutive query heads
    read, head step h at lane block ``h // rep``; ``head``: ``[B, H, Tp,
    w]``, a width that is no lane block, ``group`` heads a block (the per-row
    statistics of packed heads)."""
    def at(*grid):
        b, h, r = pos(*grid)
        r = 0 if whole else r
        if kind == "grouped":
            return b, r, h // rep
        return {"row": (b, r, 0), "lane": (b, r, h),
                "head": (b, h, r, 0)}[kind]

    return pl.BlockSpec((None, group, n, width) if kind == "head"
                        else (1, n, width), at)


def _shape(kind, b, heads, tp, width):
    if kind == "row":
        return b, tp, width
    return (b, tp, heads * width) if kind == "lane" else (b, heads, tp, width)


def _scores(a_parts, b_parts, precision):
    """sum_i a_i . b_i^T over the parts' own widths, in float32."""
    s = _dot(a_parts[0], b_parts[0], (1, 1), precision)
    for a, b in zip(a_parts[1:], b_parts[1:]):
        s = s + _dot(a, b, (1, 1), precision)
    return s


def _head_lanes(group: int, rows: int):
    """One entry for each of the ``group`` heads a grid step holds: the bool
    ``[rows, 128]`` mask of the head's lanes in a 128-lane tile that packs
    ``group`` heads side by side, or ``[None]`` where a step holds one head
    (nothing is then masked or merged, and nothing is traced for it)."""
    if group == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1)
    width = _LANE // group
    return [(lane >= j * width) & (lane < (j + 1) * width)
            for j in range(group)]


def _only(x, lanes):
    """``x`` with every lane but one head's zeroed: a dot that contracts
    all 128 lanes of it against an unmasked tile sums that head's products
    and exact zeros."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _merge(per_head, head_lanes):
    """One tile that holds, in each head's lanes, that head's result:
    ``per_head[j]`` is right in ``head_lanes[j]`` alone (its other lanes
    hold its rows against the other heads' columns, which nothing reads)."""
    out = per_head[0]
    for x, lanes in zip(per_head[1:], head_lanes[1:]):
        out = jnp.where(lanes, x, out)
    return out


def _fwd_kernel(*refs, block_k, scale, precision, causal, q_axis=1, group=1,
                window=None):
    # Mosaic layout contract (learned on real silicon, KERNELS r5): every
    # block's trailing two dims must be (8k, 128k) or equal the array dims.
    # Row-per-(batch,head) vectors therefore travel as mask [B, 1, Tp] and
    # lse/delta [.., Tp, 1] (dK/dV takes them the other way round: see
    # _bwd_call), and all in-kernel state stays 2-D. Mosaic keeps a
    # [Bq, 1] value replicated along the lanes, so m and corr meet the score
    # tile without a broadcast; l is kept as 128 per-lane partial sums and
    # crosses the lanes once, after the last block.
    n = (len(refs) - 4) // 2  # parts of q and of k whose scores add
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, mask_ref, o_ref, lse_ref = refs[2 * n:]
    qs = [_mxu_operand(r[0]) for r in q_refs]  # [Bq, w_i]
    bq, dvp = qs[0].shape[0], v_ref.shape[-1]
    # the heads of this step: one, or ``group`` that share each 128-lane
    # tile. The body below runs once a head over the SAME tiles; q is
    # masked to a head's lanes once, outside the loop over key blocks
    heads = _head_lanes(group, bq)
    qs_of = [[_only(q, lanes_j) for q in qs] for lanes_j in heads]
    lanes = _LANE if block_k % _LANE == 0 else block_k
    if causal:
        q_start = pl.program_id(q_axis) * bq

    def step(ks, carry, edge):
        # a head: m [Bq, 1], l [Bq, lanes]; acc: [Bq, Dvp], all f32.
        # ``edge``: the diagonal or the window's far edge crosses the tile
        stats, acc = carry
        kbs = [_mxu_operand(r[0, ks, :]) for r in k_refs]
        vb = _mxu_operand(v_ref[0, ks, :])
        keep = mask_ref[0, :, ks] > 0  # [1, Bk]
        if edge:
            keep = keep & _on_or_under_diagonal(q_start, bq, ks.start,
                                                block_k, False, window)
        new_stats, ps, corrs = [], [], []
        for (m, l), qs_j in zip(stats, qs_of):
            s = _scores(qs_j, kbs, precision) * scale  # [Bq, Bk]
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p[:, :lanes]
            for c in range(lanes, block_k, lanes):
                l = l + p[:, c:c + lanes]
            new_stats.append((m_new, l))
            ps.append(p)
            corrs.append(corr)
        # P_j V is right in head j's lanes: one accumulator, each lane
        # rescaled by its own head's correction
        acc = acc * _merge(corrs, heads) + _merge(
            [_dot(p, vb, (1, 0), precision) for p in ps], heads)
        return tuple(new_stats), acc

    stats, acc = _block_loop(
        causal, q_axis, bq, block_k, v_ref.shape[1], window, True, step,
        (tuple((jnp.full((bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((bq, lanes), jnp.float32)) for _ in heads),
         jnp.zeros((bq, dvp), jnp.float32)))
    denoms = [jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-20)
              for _, l in stats]
    o_ref[0] = (acc / _merge(denoms, heads)).astype(o_ref.dtype)
    for j, ((m, _), denom) in enumerate(zip(stats, denoms)):
        lse_ref[j] = m + jnp.log(denom)  # [Bq, 1]


def _fwd_call(qs, ks, v, mask, block_q, block_k, scale, interpret, causal,
              kinds, window=None):
    (q_kinds, k_kinds, v_kind, heads, group, rep, b, tp, widths, dvp, out,
     vec) = _layout_of(kinds, qs, v, mask)
    if heads is None:  # one grid row per (batch, head)
        grid, pos = (b, tp // block_q), lambda b, i: (b, 0, i)
    else:
        grid, pos = (b, heads, tp // block_q), lambda b, h, i: (b, h, i)
    kernel = functools.partial(_fwd_kernel, block_k=block_k, scale=scale,
                               precision=_dot_precision(qs[0].dtype),
                               causal=causal, q_axis=len(grid) - 1,
                               group=group, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *(_spec(kind, block_q, w, pos) for kind, w in zip(q_kinds, widths)),
            *(_spec(kind, tp, w, pos, whole=True, rep=rep)
              for kind, w in zip(k_kinds, widths)),
            _spec(v_kind, tp, dvp, pos, whole=True, rep=rep),
            _spec("row", 1, tp, pos, whole=True),
        ],
        out_specs=[
            _spec(out, block_q, dvp, pos),
            _spec(vec, block_q, 1, pos, group=group),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(_shape(out, b, heads, tp, dvp), v.dtype),
            jax.ShapeDtypeStruct(_shape(vec, b, (heads or 1) * group, tp,
                                        1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_CAUSAL_PARAMS if causal else None,
        name="flash_fwd",
    )(*qs, *ks, v, mask)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, block_k, scale, precision, causal, q_axis=1,
                   group=1, window=None):
    n = (len(refs) - 5) // 3
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, mask_ref, do_ref, lse_ref, delta_ref = refs[2 * n:2 * n + 5]
    dq_refs = refs[2 * n + 5:]
    qs = [_mxu_operand(r[0]) for r in q_refs]
    do = _mxu_operand(do_ref[0])
    bq = qs[0].shape[0]
    heads = _head_lanes(group, bq)
    # a head: its lanes of q and dO, lse [Bq, 1], delta [Bq, 1] = rowsum(dO O)
    per_head = [([_only(q, lanes_j) for q in qs], _only(do, lanes_j),
                 lse_ref[j], delta_ref[j])
                for j, lanes_j in enumerate(heads)]
    if causal:
        q_start = pl.program_id(q_axis) * bq

    def step(ks, dqs, edge):
        kbs = [_mxu_operand(r[0, ks, :]) for r in k_refs]
        vb = _mxu_operand(v_ref[0, ks, :])
        keep = mask_ref[0, :, ks] > 0  # [1, Bk]
        if edge:
            keep = keep & _on_or_under_diagonal(q_start, bq, ks.start,
                                                block_k, False, window)
        dss = []
        for qs_j, do_j, lse, delta in per_head:
            s = _scores(qs_j, kbs, precision) * scale
            p = jnp.where(keep, jnp.exp(s - lse), 0.0)
            dp = _dot(do_j, vb, (1, 1), precision)
            # dS = p * (dP - delta) * scale; the scale waits for the sum
            dss.append(p * (dp - delta))
        # dS_j K is right in head j's lanes
        return tuple(
            dq + _merge([_dot(ds, kb, (1, 0), precision) for ds in dss],
                        heads)
            for dq, kb in zip(dqs, kbs))

    dqs = _block_loop(causal, q_axis, bq, block_k, v_ref.shape[1], window,
                      True, step,
                      tuple(jnp.zeros(q.shape, jnp.float32) for q in qs))
    for dq_ref, dq in zip(dq_refs, dqs):
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _store(ref, value, head_axis, every=None):
    """Write one key block's gradient. ``head_axis`` None: the block is this
    grid step's own. Else the heads share the part, all of them (``every``
    None) or each run of ``every`` consecutive ones (grouped key/value
    heads): its block is float32 and stays in VMEM across those steps of the
    head axis (the innermost), the first head writes it and the others add
    to it."""
    if head_axis is None:
        ref[0] = value.astype(ref.dtype)
        return
    h = pl.program_id(head_axis)
    if every is not None:
        h = h % every

    @pl.when(h == 0)
    def _():
        ref[0] = value

    @pl.when(h > 0)
    def _():
        ref[0] = ref[0] + value


def _bwd_dkv_kernel(*refs, block_q, scale, precision, causal, shared,
                    k_axis=1, group=1, rep=1, window=None):
    # The score tile is held transposed, [Bk, Bq]: dV += P^T dO and
    # dK += dS^T Q are then plain row-major dots, and lse / delta meet the
    # tile as rows [1, Bq] (a sublane broadcast) where the [Bq, Bk] form
    # paid a [128, 128] transpose and 32 lane-broadcast permutes a tile
    # (18.4 -> 14.3 ms a call on the v5e, PR 26).
    n = (len(refs) - 6) // 3
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, keep_ref, do_ref, lse_ref, delta_ref = refs[2 * n:2 * n + 5]
    dk_refs, dv_ref = refs[2 * n + 5:-1], refs[-1]
    kbs = [_mxu_operand(r[0]) for r in k_refs]  # [Bk, w_i]
    vb = _mxu_operand(v_ref[0])
    bk = vb.shape[0]
    # k and v masked to a head's lanes once, outside the loop over query
    # blocks; the q / dO tiles meet them unmasked
    heads = _head_lanes(group, bk)
    per_head = [([_only(kb, lanes_j) for kb in kbs], _only(vb, lanes_j))
                for lanes_j in heads]
    if causal:
        k_start = pl.program_id(k_axis) * bk

    def step(qs_, carry, edge):
        dks, dv = carry
        qs = [_mxu_operand(r[0, qs_, :]) for r in q_refs]
        do = _mxu_operand(do_ref[0, qs_, :])
        pts, dpts, deltas = [], [], []
        for j, (kbs_j, vb_j) in enumerate(per_head):
            lse = lse_ref[j, :, qs_]  # [1, Bq]
            deltas.append(delta_ref[j, :, qs_])
            pt = jnp.exp(_scores(kbs_j, qs, precision) * scale - lse)
            if edge:
                pt = jnp.where(_on_or_under_diagonal(
                    qs_.start, block_q, k_start, bk, True, window), pt, 0.0)
            pts.append(pt)
            dpts.append(_dot(vb_j, do, (1, 1), precision))
        # P_j^T dO and dS_j^T Q are right in head j's lanes
        dv = dv + _merge([_dot(pt, do, (1, 0), precision) for pt in pts],
                         heads)
        dsts = [pt * (dpt - delta)
                for pt, dpt, delta in zip(pts, dpts, deltas)]
        return tuple(
            dk + _merge([_dot(dst, q, (1, 0), precision) for dst in dsts],
                        heads)
            for dk, q in zip(dks, qs)), dv

    dks0 = tuple(jnp.zeros(kb.shape, jnp.float32) for kb in kbs)
    # one zeros tile serves both where the widths agree: the program the
    # equal-width calls traced to before the second width
    dv0 = (dks0[0] if vb.shape == kbs[0].shape
           else jnp.zeros(vb.shape, jnp.float32))
    dks, dv = _block_loop(causal, k_axis, bk, block_q, do_ref.shape[1],
                          window, False, step, (dks0, dv0))
    # A row of dK / dV depends on its own key alone, so the key-padding mask
    # is one select on the sums: a padded key's row is zero, as when every
    # p of that key was zeroed in the loop.
    keep = keep_ref[0] > 0  # [Bk, 1]
    # shared: the k parts, then v
    def store(ref, value, kind):
        _store(ref, value, 2 if kind else None,
               rep if kind == "grouped" else None)

    for dk_ref, dk, kind in zip(dk_refs, dks, shared):
        store(dk_ref, jnp.where(keep, dk * scale, 0.0), kind)
    store(dv_ref, jnp.where(keep, dv, 0.0), shared[-1])


def _bwd_call(qs, ks, v, mask, o, lse, do, block_q, block_k, scale, interpret,
              dlse, causal, kinds, window=None):
    """``lse`` comes as the forward rule kept it, ``[.., Tp]``; ``dlse`` as
    the result's cotangent, ``[.., Tp, 1]``."""
    (q_kinds, k_kinds, v_kind, heads, group, rep, b, tp, widths, dvp, out,
     vec) = _layout_of(kinds, qs, v, mask)
    # lse is a differentiable OUTPUT (ring-flash merge): its cotangent
    # enters the score gradient as dS = p*(dP - delta + dlse), i.e. the
    # delta slot carries (delta - dlse) — kernels unchanged. Plain
    # flash_attention reaches here with dlse = zeros (custom_vjp
    # instantiates the dropped output's cotangent).
    if heads is None:
        delta = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                         axis=-1, keepdims=True)
                 - dlse.astype(jnp.float32))  # [BH, Tp, 1]
        grid, pos = (b, tp // block_q), lambda b, i: (b, 0, i)
    else:
        prod = do.astype(jnp.float32) * o.astype(jnp.float32)
        if group == 1:  # a head is whole lane tiles: a free view, a reduce
            delta = jnp.transpose(
                jnp.sum(prod.reshape(b, tp, heads, dvp), axis=-1), (0, 2, 1))
        else:
            # A packed head is 64 or 32 of a tile's 128 lanes, so [.., H, w]
            # is no free view: XLA lays the float32 product out T-minor
            # before it reduces (43 ms a round in the flash cell, PR 40). A
            # product with the heads' 0/1 membership matrix sums the same
            # lanes on the MXU where they lie; at HIGHEST the float32
            # operand is taken whole (three bf16 pieces), the matrix exact.
            n_heads, width = heads * group, dvp // group
            member = (jnp.arange(n_heads * width)[:, None] // width
                      == jnp.arange(n_heads)[None, :]).astype(jnp.float32)
            delta = jnp.einsum("btk,kh->bht", prod, member,
                               precision=jax.lax.Precision.HIGHEST)
        delta = delta[..., None] - dlse.astype(jnp.float32)  # [B, H, Tp, 1]
        grid, pos = (b, heads, tp // block_q), lambda b, h, i: (b, h, i)

    prec = _dot_precision(qs[0].dtype)
    dq_kernel = functools.partial(_bwd_dq_kernel, block_k=block_k, scale=scale,
                                  precision=prec, causal=causal,
                                  q_axis=len(grid) - 1, group=group,
                                  window=window)
    q_specs = [_spec(kind, block_q, w, pos) for kind, w in zip(q_kinds, widths)]
    dqs = pl.pallas_call(
        dq_kernel,
        grid=grid,
        in_specs=[
            *q_specs,
            *(_spec(kind, tp, w, pos, whole=True, rep=rep)
              for kind, w in zip(k_kinds, widths)),
            _spec(v_kind, tp, dvp, pos, whole=True, rep=rep),
            _spec("row", 1, tp, pos, whole=True),
            _spec(out, block_q, dvp, pos),
            _spec(vec, block_q, 1, pos, group=group),
            _spec(vec, block_q, 1, pos, group=group),
        ],
        out_specs=q_specs,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype) for q in qs],
        interpret=interpret,
        compiler_params=_CAUSAL_PARAMS if causal else None,
        name="flash_dq",
    )(*qs, *ks, v, mask, do, lse.reshape(*lse.shape, 1), delta)

    # dK/dV sees queries along the lanes: lse and delta as rows [.., 1, Tp]
    # (sliced at block_q offsets like the forward's mask), the key mask as a
    # column [B, Tp, 1]. Where every head shares a part, or every ``rep``
    # consecutive heads a key/value head, the head is the innermost grid
    # axis, so the part's float32 block stays in VMEM while the heads that
    # read it add to it (_store). Where no part is shared the key block is
    # innermost, as on the transposed path: a head step's whole-sequence Q
    # and dO are then fetched once, not once a key block.
    shared = tuple(kind if heads is not None and kind in ("row", "grouped")
                   else None for kind in (*k_kinds, v_kind))
    k_axis = 1  # where the key block is in the grid
    if heads is None:
        grid, pos = (b, tp // block_k), lambda b, j: (b, 0, j)
    elif any(shared):
        grid, pos = (b, tp // block_k, heads), lambda b, j, h: (b, h, j)
    else:
        grid, pos = (b, heads, tp // block_k), lambda b, h, j: (b, h, j)
        k_axis = 2
    dkv_kernel = functools.partial(_bwd_dkv_kernel, block_q=block_q,
                                   scale=scale, precision=prec, causal=causal,
                                   shared=shared, group=group, k_axis=k_axis,
                                   rep=rep, window=window)
    k_specs = [_spec(kind, block_k, w, pos, rep=rep)
               for kind, w in zip(k_kinds, widths)]
    v_spec = _spec(v_kind, block_k, dvp, pos, rep=rep)
    *dks, dv = pl.pallas_call(
        dkv_kernel,
        grid=grid,
        in_specs=[
            *(_spec(kind, tp, w, pos, whole=True)
              for kind, w in zip(q_kinds, widths)),
            *k_specs,
            v_spec,
            _spec("row", block_k, 1, pos),
            _spec(out, tp, dvp, pos, whole=True),
            _spec(vec, 1, tp, pos, whole=True, group=group),
            _spec(vec, 1, tp, pos, whole=True, group=group),
        ],
        out_specs=[*k_specs, v_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape,
                                        jnp.float32 if acc else x.dtype)
                   for x, acc in zip((*ks, v), shared)],
        interpret=interpret,
        compiler_params=_CAUSAL_PARAMS if causal else None,
        name="flash_dkv",
    )(*qs, *ks, v, mask.reshape(b, tp, 1), do,
      lse.reshape(*lse.shape[:-1], 1, tp),
      delta.reshape(*delta.shape[:-2], 1, tp))
    return dqs, dks, dv


# ---------------------------------------------------------------------------
# custom_vjp over the operands as the kernels address them
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_lse(qs, ks, v, mask, block_q, block_k, scale, interpret, causal,
               kinds, window):
    """(out, lse) pair with lse a first-class differentiable output so
    partial-attention results can be merged exactly (ring-flash). The
    plain-``out`` path (flash_attention) wraps this and drops lse — its
    zero cotangent makes _bwd_call's dlse term vanish, so ONE custom_vjp
    serves both APIs, and both layouts: ``qs`` / ``ks`` are tuples of parts
    laid out as ``kinds`` says (``_layout_of``; None: padded ``[BH, Tp,
    Dp]``, out the same and lse ``[BH, Tp, 1]``; else out ``[B, Tp, H*Dv]``
    and lse ``[B, H, Tp, 1]``)."""
    return _fwd_call(qs, ks, v, mask, block_q, block_k, scale, interpret,
                     causal, kinds, window)


def _flash_lse_fwd(qs, ks, v, mask, block_q, block_k, scale, interpret,
                   causal, kinds, window):
    """The forward rule names what the backward reads of it, so a remat
    policy can keep exactly that (``SAVED_NAMES``) and the layer's recompute
    holds no second ``flash_fwd``; no policy, no effect. ``lse`` is kept as
    ``[.., Tp]``: the kernel writes ``[.., Tp, 1]``, whose trailing axis of 1
    is padded 128-fold in HBM tiles, which one layer's copy can afford and a
    copy per kept layer cannot. The result's ``lse`` is a view of the kept
    one, so a recompute that reads it (ring-flash's merge) needs no kernel
    either."""
    out, lse = _fwd_call(qs, ks, v, mask, block_q, block_k, scale, interpret,
                         causal, kinds, window)
    out = named(out, FLASH_OUT)
    lse = named(lse.reshape(lse.shape[:-1]), FLASH_LSE)
    return (out, lse.reshape(*lse.shape, 1)), (qs, ks, v, mask, out, lse)


def _flash_lse_bwd(block_q, block_k, scale, interpret, causal, kinds, window,
                   res, cts):
    do, dlse = cts
    qs, ks, v, mask, out, lse = res
    dqs, dks, dv = _bwd_call(qs, ks, v, mask, out, lse, do, block_q, block_k,
                             scale, interpret, dlse=dlse, causal=causal,
                             kinds=kinds, window=window)
    # a shared part's gradient was summed over the heads in float32
    return (tuple(dqs), tuple(dk.astype(k.dtype) for dk, k in zip(dks, ks)),
            dv.astype(v.dtype), None)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# flash_attention_lse calls traced while a counter is open, by the path each
# took: a fact of the traced program, for a build-time gauge
_site_counters: list = []


@contextlib.contextmanager
def count_call_sites():
    """Counts, while open, the ``flash_attention`` / ``flash_attention_lse``
    calls TRACED, by path: ``{"lane_indexed": n, "transposed": m}``. A run
    of layers under ``lax.scan`` traces its call once. Causal calls add
    ``tiles_edge``, ``tiles_interior`` and ``cond_steps`` (``traversal`` a
    head of the newest such call: the tiles that build the positional mask,
    those that do not, and the block steps behind a condition of their own),
    and only they do. Calls under a
    ``window`` add three keys of their own, and only they do: ``window``
    (how many of the calls counted above), ``window_tiles_live`` and
    ``window_tiles_causal`` (``live_tiles`` a head of the newest such call,
    with its window and without)."""
    sites = collections.Counter(lane_indexed=0, transposed=0)
    _site_counters.append(sites)
    try:
        yield sites
    finally:
        _site_counters.remove(sites)


def _lane_kinds(qs, ks, v):
    """How each operand lies for the lane-indexed path (``_spec``'s kinds:
    (q kinds, k kinds, v kind, head steps, heads a step, query heads a
    grouped key/value head)), or None where the shapes leave the transposed
    path. A part of k, or v, a multiple of 128 lanes wide with FEWER heads
    than q (each serving ``rep`` consecutive query heads: grouped-query
    attention) is ``grouped``: the same ``[B, T, Hkv*w]`` view, query head h
    at lane block ``h // rep``. A part with one head under several
    query heads is shared (``row``, any width: its block spans its whole
    last axis); a head width that is a multiple of 128 lanes is a lane block
    of ``[B, T, H*w]`` (``lane``); a narrower part of q is laid out ``[B, H,
    T, w]`` (``head``: the one copy the path makes) if its part of k is
    shared. Packed: every part of q / k and v ``_PACKED_WIDTHS`` wide alike
    (g = 128 // w heads fill a lane block), with heads of their own, as many
    on each, a multiple of g: the same ``lane`` blocks, g heads a step.
    Anything else (an odd count of narrow heads, 80 or 96 lanes, a narrow
    part of k or a narrow v with fewer heads than q, whose heads are then
    repeated first) is the transposed path's."""
    heads = qs[0].shape[2]
    width = v.shape[3]
    if (width in _PACKED_WIDTHS and heads % (_LANE // width) == 0
            and all(x.shape[2:] == (heads, width) for x in (*qs, *ks, v))):
        group = _LANE // width
        return (("lane",) * len(qs), ("lane",) * len(ks), "lane",
                heads // group, group, 1)

    # key/value heads that each serve ``rep`` consecutive query heads
    kv_heads = {x.shape[2] for x in (*ks, v)} - {1, heads}
    if len(kv_heads) > 1 or any(heads % n for n in kv_heads):
        return None
    rep = heads // min(kv_heads, default=heads)

    def kind(x, narrow=None):
        if x.shape[2] == 1 and heads > 1:
            return "row"
        if x.shape[3] % _LANE:
            return narrow
        return "lane" if x.shape[2] == heads else "grouped"

    k_kinds = tuple(kind(k) for k in ks)
    q_kinds = tuple(kind(q, "head" if kk == "row" else None)
                    for q, kk in zip(qs, k_kinds))
    kinds = (q_kinds, k_kinds, kind(v), heads, 1, rep)
    if (v.shape[3] % _LANE or None in q_kinds + k_kinds
            or {"row", "grouped"} & set(q_kinds)):
        return None
    return kinds


def flash_attention(
    q: jax.Array | tuple,
    k: jax.Array | tuple,
    v: jax.Array,
    pad_mask: jax.Array | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Exact softmax attention, flash-style. q, k: [B, T, H, D], v:
    [B, T, H, Dv]; pad_mask: [B, T] with 1 = real token (key positions);
    returns [B, T, H, Dv]. Drop-in for ring_attention._dense_attention.

    The value head width ``Dv`` is ``v``'s own and may differ from the
    query/key width ``D`` (latent attention: 192 and 128): S = Q K^T runs at
    D, P V, dV and dP at Dv. ``scale`` (static) multiplies the scores;
    ``None`` is ``1 / sqrt(D)``.

    ``q`` and ``k`` may be tuples of parts whose scores add: ``S = sum_i q_i
    k_i^T`` (latent attention: a part without positions and a rotary part),
    summed in float32 before the scale; ``D`` is the parts' widths together
    and the gradients come back per part.

    ``causal`` (static): a query sees the keys at its own position and
    before; key blocks wholly above the diagonal are skipped in all three
    kernels. ``window`` (static, needs ``causal``): a query sees its own
    position and the ``window - 1`` before it (``0 <= i - j < window``:
    sliding-window attention), and key blocks (in dK/dV query blocks) wholly
    behind the window are skipped like those above the diagonal:
    ``live_tiles`` counts what is left; ``None`` traces what a call without
    the argument traced. ``k`` / ``v``, or a part of ``k``, with a single
    head (``[B, T, 1, D]``) is shared by every query head (multi-query attention), and its
    gradient is the sum over the query heads.

    Which of the two paths a call takes follows from its shapes (module
    docstring; ``_lane_kinds``); the results are the same.

    pad_mask is NON-differentiable: it is a binary padding indicator, and the
    custom VJP returns a zero cotangent for it (a soft/learned mask would get
    silent zero grads here — use the dense path for that; stop_gradient in
    the shared prep makes the contract explicit)."""
    out, _ = flash_attention_lse(q, k, v, pad_mask, block_q, block_k,
                                 interpret, causal, scale, window)
    return out


def flash_attention_lse(
    q: jax.Array | tuple,
    k: jax.Array | tuple,
    v: jax.Array,
    pad_mask: jax.Array | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
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
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(
            f"flash_attention: window={window!r} with causal={causal}: a "
            "window is the query's own position and the window - 1 before "
            "it, so it needs causal=True and at least 1")
    window = None if window is None else int(window)
    qs = tuple(q) if isinstance(q, (tuple, list)) else (q,)
    ks = tuple(k) if isinstance(k, (tuple, list)) else (k,)
    if len(qs) != len(ks) or any(
            a.shape[-1] != b.shape[-1] for a, b in zip(qs, ks)):
        raise ValueError(
            "flash_attention: q and k are parts whose scores add, so they "
            f"come in pairs of one width; got {[a.shape for a in qs]} and "
            f"{[b.shape for b in ks]}")
    if scale is None:
        scale = 1.0 / (sum(a.shape[-1] for a in qs) ** 0.5)
    kinds = _lane_kinds(qs, ks, v)
    for sites in _site_counters:
        sites["transposed" if kinds is None else "lane_indexed"] += 1
        if not causal:
            continue
        multiple = math.lcm(block_q, block_k)
        tp = pl.cdiv(qs[0].shape[1], multiple) * multiple
        # how a head of the newest causal call walks its live range (set,
        # not added: ``Counter.update`` would sum them over the calls)
        for fact, n in traversal(tp, block_q, block_k, window).items():
            sites[fact] = n
        if window is not None:
            # a call under a window, and the tiles a head of it executes
            # beside what ``causal`` alone would (the newest call's)
            sites["window"] += 1
            sites["window_tiles_live"] = live_tiles(tp, block_q, block_k,
                                                    window)
            sites["window_tiles_causal"] = live_tiles(tp, block_q, block_k)
    if kinds is not None:
        return _lane_indexed(qs, ks, v, pad_mask, block_q, block_k, interpret,
                             causal, scale, kinds, window)
    # the transposed path copies anyway: one part each, a part of k with one
    # head under parts with heads of their own broadcast to theirs, grouped
    # key/value heads repeated to the query heads that read them
    heads = qs[0].shape[2]

    def spread(a):
        return (jnp.repeat(a, heads // a.shape[2], axis=2)
                if 1 < a.shape[2] < heads else a)

    ks, v = [spread(a) for a in ks], spread(v)
    k_heads = max(a.shape[2] for a in ks)
    q = jnp.concatenate(qs, axis=-1)
    k = jnp.concatenate(
        [jnp.broadcast_to(a, (*a.shape[:2], k_heads, a.shape[3])) for a in ks],
        axis=-1)
    return _transposed(q, k, v, pad_mask, block_q, block_k, interpret, causal,
                       scale, window)


def _sharded(call, n_operands: int):
    """Inside a program traced for a mesh (parallel/program.py): XLA cannot
    auto-partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so the call goes manual. The specs say
    "replicated" for these per-client operands; the engine's vmap over
    clients (spmd_axis_name="clients") turns its batched axis into
    P("clients"), so each chip runs the kernel on its own clients only.
    Already-manual contexts (ring attention's shard_map body) call the
    kernel directly."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.are_all_axes_auto:
        return call
    return jax.shard_map(call, mesh=mesh, in_specs=(P(),) * n_operands,
                         out_specs=(P(), P()), check_vma=False)


def _lane_indexed(qs, ks, v, pad_mask, block_q, block_k, interpret, causal,
                  scale, kinds, window=None):
    """The operands where the model holds them: nothing is transposed,
    padded or broadcast but a narrow part of q (``_lane_kinds``) and, where
    the blocks do not divide it, the sequence."""
    q_kinds, k_kinds, v_kind, steps, _, rep = kinds
    b, t, h = qs[0].shape[:3]
    dv = v.shape[-1]
    t_multiple = math.lcm(block_q, block_k)

    def lay(x, kind):
        x = (jnp.transpose(x, (0, 2, 1, 3)) if kind == "head"
             else x.reshape(b, t, -1))
        return _pad_axis(x, x.ndim - 2, t_multiple)

    qs = tuple(lay(x, kind) for x, kind in zip(qs, q_kinds))
    ks = tuple(lay(x, kind) for x, kind in zip(ks, k_kinds))
    v = lay(v, v_kind)
    tp = v.shape[1]
    if not interpret:
        _check_compilable(
            block_q, block_k, tp,
            sum(pl.cdiv(a.shape[-1] // _lane_blocks(kind, steps, rep),
                        _LANE) * _LANE for a, kind in zip(ks, k_kinds)),
            dv, v.dtype)
    if pad_mask is None:
        pad_mask = jnp.ones((b, t), jnp.float32)
    pad_mask = jax.lax.stop_gradient(pad_mask)
    # [B, 1, Tp]: one row a batch entry, every head's block is the same one
    maskp = _pad_axis(pad_mask.astype(jnp.float32), 1, t_multiple)[:, None, :]

    def call(qs, ks, v, maskp):
        return _flash_lse(qs, ks, v, maskp, block_q, block_k, scale,
                          interpret, causal, kinds, window)

    out, lse = _sharded(call, 4)(qs, ks, v, maskp)
    return out[:, :t].reshape(b, t, h, dv), lse[:, :, :t, 0]


def _transposed(q, k, v, pad_mask, block_q, block_k, interpret, causal,
                scale, window=None):
    """Every operand copied to ``[B*H, T, Dpadded]``."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if k.shape[2] == 1 and h > 1:
        k = jnp.broadcast_to(k, q.shape)
        v = jnp.broadcast_to(v, (*q.shape[:3], dv))
    if pad_mask is None:
        pad_mask = jnp.ones((b, t), jnp.float32)
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
        return _flash_lse((qp,), (kp,), vp, maskp, block_q, block_k, scale,
                          interpret, causal, None, window)

    out, lse = _sharded(padded, 4)(qp, kp, vp, maskp)
    out = out[:, :t, :dv].reshape(b, h, t, dv)
    lse = lse[:, :t, 0].reshape(b, h, t)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype), lse
