"""Latent-attention / routed-expert decoder for federated adapter fine-tuning
(the DeepSeek-V2 family: DeepSeek-AI 2024, arXiv:2405.04434; HF
``model_type: deepseek_v2``).

Parity surface: /root/reference/examples/fedllm_example — LoRA adapters
trained federally over a frozen causal LM that every client loads once.

Layer ``l``: ``h <- h + MLA(RMSNorm(h))`` then ``h <- h + FFN_l(RMSNorm(h))``.

MLA (multi-head latent attention, training form: no absorption, no cache):
``c_q = RMSNorm(u W_qa)``, ``[q_nope | q_pe] = c_q W_qb`` per head;
``[c_kv | k_pe] = u W_kva``, ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` per
head; ``q_pe`` and the ONE ``k_pe`` all heads share get rotary positions
(YaRN's blended frequencies, halves layout); ``q = [q_nope | q_pe]``, ``k =
[k_nope | k_pe]`` are ``qk_nope + qk_rope`` wide, ``v`` is ``v_head_dim``
wide; causal softmax at ``(qk_nope + qk_rope)^-0.5 * mscale^2``; ``W_o``.
Neither ``q`` nor ``k`` is ever assembled: the score is ``q_nope k_nope^T +
q_pe k_pe^T``, the attention function takes the parts, and each part comes
out of a projection of its own columns (``_head_columns``).

FFN: the first ``first_k_dense`` layers a dense SwiGLU; the others
``sum_i w_i E_i(u) + SwiGLU_shared(u)`` with the router of
``group_limited_greedy``: softmax over all ``n_routed_experts`` in float32,
the best ``topk_group`` of ``n_group`` groups by their largest score, the
``top_k`` largest scores inside them, ``w_i = routed_scale * s_i`` (not
renormalised). The module is told which experts it HOLDS (``experts_held``
from ``first_expert_held``: a chip's share under expert parallelism); it
routes over all of them and computes the held experts' part of the sum only,
which is what goes on to the next layer.

The routed part is dropless and does work in proportion to the assignments
(``routed_experts``): the (token, choice) pairs that chose a held expert are
sorted by expert (``_plan``: ONE permutation into expert order); each expert
owns tiles of ``TILE_ROWS`` of its sorted rows, as many as its count needs,
and walks them in chunks of ``_chunk_rows`` rows, as many chunks as its tiles
need (a loop with a data-dependent trip count). A chunk moves its rows once
in and once out: ONE gather ``x[tokens]`` into a contiguous buffer with a
slot of ``TILE_ROWS`` rows a tile, then the expert's tiles in the chunk (a
second data-dependent loop; the expert's matrices stay where the outer loop
put them) on slices of that buffer, their weighted results written to the
same slots of a second buffer, then ONE combine of the buffer into the
float32 sum (``kernels/row_combine.py add_rows``: a Mosaic call of row copies
where the rows are whole 128-lane tiles, the sum kept ``[N, d / 128, 128]``
between the chunks; XLA's scatter-add for narrower rows), a row that is no
tile's own skipped. No tile gathers from or adds into an ``[N, d]`` array;
the backward does the same with ``x`` and ``dy`` gathered and ``dx``
combined once a chunk. Nothing has a capacity: every pair could be local and
the loops would run that many chunks and tiles. A token's choices are
distinct experts (a top-k), so inside a chunk no token repeats. The
held experts are a frozen base here: the function's VJP gives the
gradients of the tokens and of the combine weights (through which the router's
input trains upstream adapters) and NONE for the expert matrices. Because the
experts carry no client axis, a ``vmap`` over clients is met by folding the
client axis into the rows (``jax.custom_batching.custom_vmap``): one sort and
one set of loops over all clients' tokens.

Built the way ``models/jamba.py`` is (a named parameter tree declared by a
flax module, pure functions over one layer's dict, runs of layers as
``lax.scan``s rematerialised under ``remat``, ``per_client_param`` /
``bind_shared`` for the engine), on ``models/decoder_common.py``. What a
rematerialised layer keeps (``decoder_common.DEEPSEEK_REMAT_KEEPS``): the
flash calls' ``out`` / ``lse`` and the stream ``h + MLA(...)``, so its
recompute runs no flash forward and no product of ``o_proj``'s kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fl4health_tpu.core.pytree import merge_trees
from fl4health_tpu.core.remat import named
from fl4health_tpu.kernels import row_combine
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, lora_dense, rms_norm,
                                                 swiglu)
from fl4health_tpu.observability.stages import layer as part

# rows of one expert's tile: [TILE_ROWS, d] x [d, f] reads the expert's matrix
# once per tile, so a tile should hold an expert's usual load whole (about 154
# rows at 4,096 tokens, 8 of 160 experts held) and no more
TILE_ROWS = 256
# the most rows the routed layer gathers, runs one expert's tiles over and
# combines at once (``_chunk_rows``): 16 tiles; at the widest rows in use
# ([., 2,048] bfloat16 in, float32 out) such a chunk's rows and results are
# 17 + 34 MB of scratch, the backward's rows, cotangents and gradients 17 + 34
# + 34. A larger chunk saves nothing a row: the gather and the combine cost by
# the row (0.03 and 0.05 microseconds on a v5e), not by the call
CHUNK_ROWS = 4096


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN (Peng et al. 2023) as HF ``DeepseekV2YarnRotaryEmbedding`` has
    it; ``factor`` 1 is plain rotary embedding."""

    theta: float = 10000.0
    factor: float = 1.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_position: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, rope: RopeScaling) -> list[float]:
    """The ``dim // 2`` inverse frequencies: interpolated (divided by
    ``factor``) below the correction range, as published above it, a linear
    ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(rope.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    extra = [rope.theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope.factor <= 1:
        return extra
    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / rope.factor * ramp + f * (1.0 - ramp))
    return out


def softmax_scale(qk_head_dim: int, rope: RopeScaling) -> float:
    m = yarn_get_mscale(rope.factor, rope.mscale_all_dim)
    return qk_head_dim ** -0.5 * m * m


@dataclasses.dataclass(frozen=True)
class DeepseekDims:
    """The sizes and static choices the layer functions read."""

    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    experts_held: int
    first_expert_held: int
    n_group: int
    topk_group: int
    top_k: int
    routed_scale: float
    rope: RopeScaling
    rms_eps: float
    lora_scale: float  # alpha / rank (0 without adapters)
    dtype: Any
    attention_fn: Any


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------

def rope_tables(t: int, dim: int, rope: RopeScaling):
    """(cos, sin) [T, dim // 2], float32, times YaRN's cos/sin scale."""
    inv = jnp.asarray(yarn_inv_freq(dim, rope), F32)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    m = (yarn_get_mscale(rope.factor, rope.mscale)
         / yarn_get_mscale(rope.factor, rope.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin):
    """x [B, T, H, dim] in the halves layout ``[x1 | x2]`` -> ``[x1 cos - x2
    sin | x2 cos + x1 sin]``, computed in float32. (HF permutes each
    interleaved pair to this layout first; with seeded weights that is a
    relabelling of ``W_qb``'s and ``W_kva``'s columns.)"""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _head_columns(p, widths):
    """A projection whose output is ``n_heads`` groups of ``sum(widths)``
    columns, as one projection per width: part ``i`` holds every head's
    columns ``[offset_i, offset_i + widths[i])``, heads outermost. Slicing
    the matrix (and the adapter's ``lora_b``) instead of the product leaves
    each part ``[.., n_heads * width]`` the way its matmul writes it, which is
    where the flash calls read it; a column of a product is the same sum
    either way."""
    def cut(m, lo, w):
        return m.reshape(m.shape[0], -1, sum(widths))[:, :, lo:lo + w].reshape(
            m.shape[0], -1)

    offsets = [sum(widths[:i]) for i in range(len(widths))]
    return [{k: cut(m, lo, w) if k in ("kernel", "lora_b") else m
             for k, m in p.items()} for lo, w in zip(offsets, widths)]


def mla_attention(p, u, pad_mask, dims: DeepseekDims):
    """``dims.attention_fn((q_nope, q_pe), (k_nope, k_pe), v, pad_mask=mask,
    scale=s) -> out`` must be causal, add the scores of the two parts (the
    rotary part of k has ONE head, which every query head shares) and take a
    value width of its own, e.g. ``functools.partial(kernels.flash_attention,
    causal=True, block_q=512, block_k=512)``; ``None`` is the dense form.
    Nothing is concatenated, broadcast or sliced on the way: every part is
    ``[B, T, heads, width]`` as a view of what its projection wrote."""
    with part("mla_attention"):
        b, t = u.shape[:2]
        h, nope, rot = dims.n_heads, dims.qk_nope, dims.qk_rope
        c_q = rms_norm(lora_dense(p["q_a_proj"], u, dims),
                       p["q_a_layernorm"]["scale"], dims.rms_eps)
        q_nope, q_pe = (lora_dense(part, c_q, dims).reshape(b, t, h, -1)
                        for part in _head_columns(p["q_b_proj"], (nope, rot)))
        c_kv, k_pe = jnp.split(lora_dense(p["kv_a_proj_with_mqa"], u, dims),
                               [dims.kv_lora_rank], axis=-1)
        c_kv = rms_norm(c_kv, p["kv_a_layernorm"]["scale"], dims.rms_eps)
        k_nope, v = (lora_dense(part, c_kv, dims).reshape(b, t, h, -1)
                     for part in _head_columns(p["kv_b_proj"],
                                               (nope, dims.v_head)))
        cos, sin = rope_tables(t, rot, dims.rope)
        q_pe = apply_rope(q_pe, cos, sin)
        k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)
        attend = dims.attention_fn or common.dense_causal_attention
        with part("mla_flash"):
            out = attend((q_nope, q_pe), (k_nope, k_pe), v, pad_mask=pad_mask,
                         scale=softmax_scale(nope + rot, dims.rope))
        return lora_dense(p["o_proj"], out.reshape(b, t, h * dims.v_head),
                          dims)


# ---------------------------------------------------------------------------
# The routed-expert layer
# ---------------------------------------------------------------------------

def route(p, u, dims: DeepseekDims):
    """``group_limited_greedy`` over ALL the layer's experts, in float32 at
    full precision (a near tie decides which expert a token gets): u [N, d]
    -> (idx [N, top_k] int32, w [N, top_k] float32 = routed_scale * score).
    The router's matrix is [n_group, d, experts per group]: expert ``g * per
    + e`` is column ``e`` of group ``g``."""
    with part("moe_router"):
        n = u.shape[0]
        logits = jnp.einsum("nd,gde->nge", u.astype(F32),
                            p["kernel"].astype(F32),
                            precision=jax.lax.Precision.HIGHEST)
        per = logits.shape[-1]
        scores = jax.nn.softmax(logits.reshape(n, -1), axis=-1)
        best = scores.reshape(n, dims.n_group, per).max(axis=-1)
        _, groups = jax.lax.top_k(best, dims.topk_group)
        keep = jnp.zeros((n, dims.n_group), bool).at[
            jnp.arange(n)[:, None], groups].set(True)
        kept = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
        w, idx = jax.lax.top_k(kept, dims.top_k)
        return idx.astype(jnp.int32), w * dims.routed_scale


def sigmoid_route(p, u, top_k: int, routed_scale: float):
    """The sigmoid scoring rule (``models/nemotron_h.py``,
    ``models/afmoe.py``) over ALL the layer's experts, in float32 at full
    precision (a near tie decides which expert a token gets): u [N, d] ->
    (idx [N, top_k] int32, w [N, top_k] float32). The selection bias
    (``p["e_score_correction_bias"]``) enters the choice and not the weight;
    the chosen scores are renormalised, then scaled."""
    with part("moe_router"):
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(F32), p["kernel"].astype(F32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"], top_k)
        chosen = jnp.take_along_axis(scores, idx, axis=1)
        w = routed_scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w


def swiglu_expert(x, gate, up, down):
    """An expert body: SwiGLU over the expert's rows, in the rows' type
    (three matrices an expert: this family's)."""
    with part("moe_experts"):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def relu2_expert(x, up, down):
    """An expert body: ``relu(x W_up)^2 W_down`` over the expert's rows (two
    matrices an expert: ``models/nemotron_h.py``'s, on the latent width)."""
    with part("moe_experts"):
        return jnp.square(jax.nn.relu(x @ up)) @ down


def _plan(idx, w, first: int, held: int):
    """The (token, choice) pairs that chose a held expert, sorted by expert:
    (order [N*K] the sorted pairs' flat positions, tok their tokens, w_sorted
    their combine weights, both [N*K + TILE_ROWS], starts [held], counts
    [held]). Pairs for experts held elsewhere sort behind every held expert's
    rows and belong to no count."""
    k = idx.shape[1]
    key = jnp.where((idx >= first) & (idx < first + held), idx - first,
                    held).reshape(-1)
    order = jnp.argsort(key)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    # a tile may reach past the last pair: TILE_ROWS rows of padding keep
    # every dynamic slice in range (a clamped start would shift the tile)
    tok = jnp.pad((order // k).astype(jnp.int32), (0, TILE_ROWS))
    w_sorted = jnp.pad(w.reshape(-1)[order], (0, TILE_ROWS))
    return order, tok, w_sorted, jnp.cumsum(counts) - counts, counts


def _chunk_rows(n: int) -> int:
    """Rows a chunk of a call over ``n`` tokens holds: a sixteenth of the
    tokens in whole tiles, at least one tile and at most ``CHUNK_ROWS``. A
    chunk holds tiles of ONE expert, so it should hold an expert's usual
    load whole and little more (the three cells' experts see 2,048 / 352 /
    154 rows of 32,768 / 8,192 / 4,096 tokens at most: 8 / 2 / 1 tiles)."""
    return min(CHUNK_ROWS, max(TILE_ROWS, n // 16 // TILE_ROWS * TILE_ROWS))


def _tiles(count):
    return (count + TILE_ROWS - 1) // TILE_ROWS


def _chunk(c, per, n, tok, w_sorted, start, count):
    """Chunk ``c`` of the expert whose sorted rows are [start, start +
    count): its tiles ``[c * per, (c + 1) * per)``, one slot of TILE_ROWS
    rows each, a tile's rows what they always were (``start + t *
    TILE_ROWS`` onwards): (how many of the slots hold a tile; each slot's
    first sorted row; its rows' tokens [per, TILE_ROWS]; the same with every
    row that is not the slot's own sent to ``n``, past every token, so that
    the combine skips it; the rows' combine weights; which rows are a
    slot's own)."""
    t = c * per + jnp.arange(per)
    own = jnp.clip(count - t * TILE_ROWS, 0, TILE_ROWS)
    pos = jnp.where(own > 0, start + t * TILE_ROWS, 0)

    def rows_of(a):
        return jax.vmap(lambda p: jax.lax.dynamic_slice(a, (p,),
                                                        (TILE_ROWS,)))(pos)

    toks = rows_of(tok)
    live = jnp.arange(TILE_ROWS)[None, :] < own[:, None]
    return (jnp.clip(_tiles(count) - c * per, 0, per), pos, toks,
            jnp.where(live, toks, n), rows_of(w_sorted), live)


def _routed_fwd(first, body, n, x, idx, w, *experts):
    """x [N, d], idx / w [N, K], experts = ``n`` matrices per held expert, in
    ``body``'s order -> sum over the held experts chosen of w * body(x,
    *matrices), [N, d] float32."""
    held = len(experts) // n
    tokens, d = x.shape
    per = _chunk_rows(tokens) // TILE_ROWS
    slab = row_combine.slab(d)
    _, tok, w_sorted, starts, counts = _plan(idx, w, first, held)
    carry = (jnp.zeros((tokens, *slab), F32),
             jnp.zeros((per * TILE_ROWS, *slab), F32))
    for e in range(held):
        def chunk(c, carry, e=e):
            y, ys = carry
            tiles, _, toks, live_toks, wc, live = _chunk(
                c, per, tokens, tok, w_sorted, starts[e], counts[e])
            xs = x[toks.reshape(-1)]

            def tile(s, ys):
                xt = jax.lax.dynamic_slice(xs, (s * TILE_ROWS, 0),
                                           (TILE_ROWS, d))
                out = body(xt, *experts[n * e:n * e + n]).astype(F32)
                out = jnp.where(live[s][:, None], out * wc[s][:, None], 0.0)
                return jax.lax.dynamic_update_slice(
                    ys, out.reshape(TILE_ROWS, *slab),
                    (s * TILE_ROWS,) + (0,) * len(slab))

            ys = jax.lax.fori_loop(0, tiles, tile, ys)
            return row_combine.add_rows(y, live_toks.reshape(-1), ys,
                                        TILE_ROWS), ys

        carry = jax.lax.fori_loop(0, (_tiles(counts[e]) + per - 1) // per,
                                  chunk, carry)
    return carry[0].reshape(tokens, d)


def _routed_bwd(first, body, n, x, idx, w, dy, *experts):
    """(dx [N, d] float32, dw [N, K] float32) of ``_routed_fwd``: the same
    chunks and tiles, each tile recomputing its expert's forward."""
    held = len(experts) // n
    tokens, d = x.shape
    per = _chunk_rows(tokens) // TILE_ROWS
    slab = row_combine.slab(d)
    order, tok, w_sorted, starts, counts = _plan(idx, w, first, held)
    carry = (jnp.zeros((tokens, *slab), F32),
             jnp.zeros((per * TILE_ROWS, *slab), F32),
             jnp.zeros(w_sorted.shape, F32))
    for e in range(held):
        def chunk(c, carry, e=e):
            dx, dxs, dw_sorted = carry
            tiles, pos, toks, live_toks, wc, live = _chunk(
                c, per, tokens, tok, w_sorted, starts[e], counts[e])
            xs, dys = x[toks.reshape(-1)], dy[toks.reshape(-1)]

            def tile(s, carry):
                dxs, dw_sorted = carry
                at = (s * TILE_ROWS, 0)
                _, vjp = jax.vjp(
                    lambda xr, wr: body(xr, *experts[n * e:n * e + n]).astype(
                        F32) * wr[:, None],
                    jax.lax.dynamic_slice(xs, at, (TILE_ROWS, d)), wc[s])
                dxr, dwr = vjp(jnp.where(
                    live[s][:, None],
                    jax.lax.dynamic_slice(dys, at, (TILE_ROWS, d)), 0.0))
                old = jax.lax.dynamic_slice(dw_sorted, (pos[s],),
                                            (TILE_ROWS,))
                return (jax.lax.dynamic_update_slice(
                    dxs, dxr.astype(F32).reshape(TILE_ROWS, *slab),
                    (s * TILE_ROWS,) + (0,) * len(slab)),
                        jax.lax.dynamic_update_slice(
                            dw_sorted, jnp.where(live[s], dwr, old),
                            (pos[s],)))

            dxs, dw_sorted = jax.lax.fori_loop(0, tiles, tile,
                                               (dxs, dw_sorted))
            return (row_combine.add_rows(dx, live_toks.reshape(-1), dxs,
                                         TILE_ROWS), dxs, dw_sorted)

        carry = jax.lax.fori_loop(0, (_tiles(counts[e]) + per - 1) // per,
                                  chunk, carry)
    dx, _, dw_sorted = carry
    dw = jnp.zeros(order.shape, F32).at[order].set(
        dw_sorted[:order.shape[0]])
    return dx.reshape(tokens, d), dw.reshape(w.shape)


def _fold_clients(fn, n_row_args: int):
    """``fn(*row_args, *experts)`` whose first ``n_row_args`` arguments and
    every result have the rows as their leading axis, with a ``vmap`` rule
    that folds a batch axis of the row arguments into the rows: the experts
    carry no client axis, so C clients' tokens are ONE call's rows (routing
    is per token: the mathematics is ``vmap``'s). Experts that do carry the
    axis get the plain ``vmap``."""
    folded = jax.custom_batching.custom_vmap(fn)

    @folded.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_row_args:]):
            out = jax.vmap(fn, in_axes=[0 if b else None for b in in_batched]
                           )(*args)
        else:
            rows = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                    for a, b in zip(args[:n_row_args], in_batched)]
            out = folded(*(a.reshape(-1, *a.shape[2:]) for a in rows),
                         *args[n_row_args:])
            out = jax.tree_util.tree_map(
                lambda a: a.reshape(axis_size, -1, *a.shape[1:]), out)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return folded


@functools.lru_cache(maxsize=None)
def _routed_fn(first: int, body, n: int):
    fwd = _fold_clients(functools.partial(_routed_fwd, first, body, n), 3)
    bwd = _fold_clients(functools.partial(_routed_bwd, first, body, n), 4)

    @jax.custom_vjp
    def routed(x, idx, w, *experts):
        return fwd(x, idx, w, *experts)

    def routed_fwd(x, idx, w, *experts):
        return fwd(x, idx, w, *experts), (x, idx, w, experts)

    def routed_bwd(res, dy):
        x, idx, w, experts = res
        dx, dw = bwd(x, idx, w, dy, *experts)
        # the held experts are a frozen base: no gradient (module docstring)
        return (dx.astype(x.dtype), None, dw) + (None,) * len(experts)

    routed.defvjp(routed_fwd, routed_bwd)
    return routed


def routed_experts(x, idx, w, experts, first_expert_held: int,
                   body=swiglu_expert):
    """sum_k [idx_k held here] * w_k * E_{idx_k}(x): x [N, d] in the compute
    type, idx [N, K] over all the layer's experts (a token's K choices
    distinct, as a top-k's are; -1 or any index held elsewhere picks
    nothing here), w [N, K] float32,
    ``experts`` the held ones' matrices in order from ``first_expert_held``,
    each a tuple in the order ``body(rows, *matrices)`` takes them (``(gate
    [d, f], up [d, f], down [f, d])`` for ``swiglu_expert``). ``body`` is a
    module-level function (it keys the cached ``custom_vjp``). Returns [N,
    d] float32."""
    flat = [m for mats in experts for m in mats]
    return _routed_fn(int(first_expert_held), body, len(experts[0]))(
        x, idx, w, *flat)


def routed_gauges(tokens: int, top_k: int, held: int, total: int) -> dict:
    """How the held rows of a folded call over ``tokens`` tokens travel, for
    a family's ``build_gauges``: the tile's and the chunk's rows, and the
    long moves (one gather and one combine a chunk) a forward layer-pass
    emits at the expected load, ``tokens * top_k / total`` rows an expert."""
    size = _chunk_rows(tokens)
    tiles = max(1, -(-(tokens * top_k // total) // TILE_ROWS))
    return {"moe_tile_rows": TILE_ROWS, "moe_chunk_rows": size,
            "moe_row_moves_per_pass":
                2 * held * -(-tiles // (size // TILE_ROWS))}


def routed_layer(x, u, router, experts, first_expert_held: int, rule,
                 body=swiglu_expert):
    """The routed part of an expert layer, both families' one
    implementation: ``rule(router, u) -> (idx [N, K] int32 over ALL the
    layer's experts, w [N, K] float32)`` is the family's scoring rule over
    the router's input ``u`` [N, d_router] (``route`` here: softmax, the
    group limit, unnormalised; ``sigmoid_route``:
    sigmoid, a selection bias, renormalised and scaled), ``body`` its expert
    (``swiglu_expert`` / ``relu2_expert``) over the rows ``x`` [N, d] the
    experts read (``u`` itself, or a latent of it), cast here to the
    experts' type. The plan, the tiles, the client fold and the
    frozen-expert VJP are ``routed_experts``'."""
    idx, w = rule(router, u)
    return routed_experts(x.astype(experts[0][0].dtype), idx, w, experts,
                          first_expert_held, body)


def moe(p, u, dims: DeepseekDims):
    """The routed layer's part held here plus the shared experts."""
    dt = dims.dtype
    flat = u.reshape(-1, u.shape[-1])
    with part("moe"):
        experts = [tuple(p[f"experts_{j}"][name]["kernel"].astype(dt)
                         for name in ("gate_proj", "up_proj", "down_proj"))
                   for j in range(dims.experts_held)]
        y = routed_layer(flat, flat, p["gate"], experts,
                         dims.first_expert_held,
                         lambda router, u: route(router, u, dims))
    with part("shared_experts"):
        shared = swiglu(p["shared_experts"], u, dims)
    return y.reshape(u.shape).astype(dt) + shared


def layer(p, h, pad_mask, routed: bool, dims: DeepseekDims):
    u = rms_norm(h, p["input_layernorm"]["scale"], dims.rms_eps)
    # named for the remat site: with the stream kept, the layer's second half
    # is recomputed from it and o_proj's product is in no recompute
    h = named(h + mla_attention(p["self_attn"], u, pad_mask, dims),
              common.MLA_STREAM)
    u = rms_norm(h, p["post_attention_layernorm"]["scale"], dims.rms_eps)
    if routed:
        return h + moe(p["mlp"], u, dims)
    with part("mlp"):
        ff = swiglu(p["mlp"], u, dims)
    return h + ff


# ---------------------------------------------------------------------------
# The module
# ---------------------------------------------------------------------------

class DeepseekV2Classifier(nn.Module):
    """Input: integer token ids [B, T], id 0 = padding at the tail. The head
    is HF ``DeepseekV2ForSequenceClassification``'s: the final-norm hidden
    state at the last non-pad token through ``score`` (no bias)."""

    vocab_size: int
    n_classes: int
    d_model: int = 64
    n_layers: int = 3
    first_k_dense: int = 1
    d_ff: int = 128  # the leading dense layers' SwiGLU
    n_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    d_expert: int = 32  # one routed expert's SwiGLU
    n_routed_experts: int = 16  # the router's width
    experts_held: int = 16
    first_expert_held: int = 0
    n_shared_experts: int = 2  # one SwiGLU of n_shared_experts * d_expert
    n_group: int = 4
    topk_group: int = 2
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    rope: RopeScaling = RopeScaling()
    rms_eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = jnp.float32
    remat: bool = False  # rematerialise each layer on the backward pass
    attention_fn: Any = None  # causal, value width of its own; None = dense

    # -- structure ----------------------------------------------------------
    @property
    def dims(self) -> DeepseekDims:
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"{self.n_routed_experts} experts do not divide "
                             f"into {self.n_group} groups")
        if not (0 <= self.first_expert_held and self.first_expert_held
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.experts_held - 1} are not "
                f"among the router's {self.n_routed_experts}")
        return DeepseekDims(
            self.d_model, self.n_heads, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.experts_held, self.first_expert_held, self.n_group,
            self.topk_group, self.top_k,
            self.routed_scaling_factor, self.rope, self.rms_eps,
            self.lora_alpha / self.lora_rank if self.lora_rank else 0.0,
            self.dtype, self.attention_fn)

    def runs(self) -> list[list[int]]:
        """The leading dense layers, then the expert layers: [[0], [1..4]]."""
        k = min(self.first_k_dense, self.n_layers)
        return [r for r in (list(range(k)), list(range(k, self.n_layers)))
                if r]

    def _layer_spec(self, routed: bool) -> tuple:
        d, r, h = self.d_model, self.lora_rank, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        proj, norm = common.proj_spec, common.norm_spec

        def mlp(width, rank):
            return (proj("gate_proj", d, width, rank),
                    proj("up_proj", d, width, rank),
                    proj("down_proj", width, d, rank))

        attn = ("self_attn", (
            proj("q_a_proj", d, self.q_lora_rank, r),
            ("q_a_layernorm", norm(self.q_lora_rank)),
            proj("q_b_proj", self.q_lora_rank, h * qk, r),
            proj("kv_a_proj_with_mqa", d,
                 self.kv_lora_rank + self.qk_rope_head_dim, r),
            ("kv_a_layernorm", norm(self.kv_lora_rank)),
            proj("kv_b_proj", self.kv_lora_rank,
                 h * (self.qk_nope_head_dim + self.v_head_dim), r),
            proj("o_proj", h * self.v_head_dim, d, r)))
        if routed:
            # routed experts and the router are frozen and unadapted; every
            # expert's leaves have names of their own
            ffn = (("gate", (("kernel", (
                (self.n_group, d, self.n_routed_experts // self.n_group),
                "matrix")),)),
                   *((f"experts_{j}", mlp(self.d_expert, 0))
                     for j in range(self.experts_held)),
                   ("shared_experts",
                    mlp(self.n_shared_experts * self.d_expert, r)))
        else:
            ffn = mlp(self.d_ff, r)
        return (("input_layernorm", norm(d)), attn,
                ("post_attention_layernorm", norm(d)), ("mlp", ffn))

    # -- forward ------------------------------------------------------------
    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # no dropout, no batch statistics
        d = self.d_model
        spec = [("embed_tokens", (("embedding", ((self.vocab_size, d),
                                                 "embed")),)),
                ("norm", common.norm_spec(d)),
                ("score", (("kernel", ((d, self.n_classes), "matrix")),))]
        spec += [(f"layers_{i}", self._layer_spec(i >= self.first_k_dense))
                 for i in range(self.n_layers)]
        params = {name: common.Leaves(entry, name=name)()
                  for name, entry in spec}
        return self.forward(common.stack_runs(params, self.runs()), x)

    def forward(self, stacked, x):
        """``stacked``: the tree with its layers stacked by
        ``decoder_common.stack_runs``; each run is one ``lax.scan``."""
        dims = self.dims
        pad_mask = (x > 0).astype(F32)
        h = common.embed_tokens(stacked["embed_tokens"]["embedding"], x,
                                self.dtype)
        for k, run in enumerate(self.runs()):
            routed = run[0] >= self.first_k_dense

            def body(h_, p, routed=routed):
                return layer(p, h_, pad_mask, routed, dims).astype(
                    self.dtype), None

            body = common.remat_layers(body, self.remat,
                                       common.DEEPSEEK_REMAT_KEEPS)
            h, _ = jax.lax.scan(body, h, stacked["runs"][str(k)])
        return common.last_token_logits(
            h, pad_mask, stacked["norm"]["scale"], stacked["score"]["kernel"],
            self.rms_eps)

    # -- the split of the parameters (clients/engine.py ModelDef) ----------
    def per_client_param(self, path: str) -> bool:
        return common.PER_CLIENT(path)

    def bind_shared(self, shared):
        """``(per_client, x) -> (preds, features)`` over the base prepared
        once: every projection's and expert's ``kernel`` in the compute type
        (the router's stays float32, as the norms and the embedding do), the
        layers stacked over their runs."""
        with part("shared_cast"):
            prepared = common.prepare_shared(
                shared, self.runs(), self.dtype,
                lambda names: names[-1] == "kernel" and names[-2] != "gate")
        return lambda per_client, x: self.forward(
            merge_trees(prepared, common.stack_runs(per_client, self.runs())),
            x)

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Static facts of the routed layer, which path the forward's flash
        calls take and what the remat sites keep, for the simulation's
        build-time gauges; ``batch_shape`` is one client's [B, T]."""
        tokens = n_clients * math.prod(batch_shape)
        return {"moe_experts_held": self.experts_held,
                "moe_experts_total": self.n_routed_experts,
                # rows the folded routed call is built to take: every choice
                # of every token could be a held expert
                "moe_assignment_rows_bound":
                    tokens * min(self.top_k, self.experts_held),
                **routed_gauges(tokens, self.top_k, self.experts_held,
                                self.n_routed_experts),
                **common.attention_gauges(self, batch_shape, n_clients,
                                          common.DEEPSEEK_REMAT_KEEPS)}
