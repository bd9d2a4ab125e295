"""The dropless routed-expert layer of the decoder families (DeepSeek-V2's,
Nemotron-H's in a latent, AFMoE's): a scoring rule and an expert body go in,
the part of ``sum_k w_k E_{idx_k}(x)`` that the experts HELD here owe comes
out. A family brings its rule (``deepseek.route``; ``sigmoid_route`` here, two
families') and names its body (``swiglu_expert`` / ``relu2_expert``).

The layer is dropless and does work in proportion to the assignments
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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fl4health_tpu.kernels import row_combine
from fl4health_tpu.models.decoder_common import F32
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
    (three matrices an expert: ``models/deepseek.py``'s and
    ``models/afmoe.py``'s)."""
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
    """The routed part of an expert layer, the families' one
    implementation: ``rule(router, u) -> (idx [N, K] int32 over ALL the
    layer's experts, w [N, K] float32)`` is the family's scoring rule over
    the router's input ``u`` [N, d_router] (``deepseek.route``: softmax, the
    group limit, unnormalised; ``sigmoid_route``:
    sigmoid, a selection bias, renormalised and scaled), ``body`` its expert
    (``swiglu_expert`` / ``relu2_expert``) over the rows ``x`` [N, d] the
    experts read (``u`` itself, or a latent of it), cast here to the
    experts' type. The plan, the tiles, the client fold and the
    frozen-expert VJP are ``routed_experts``'."""
    idx, w = rule(router, u)
    return routed_experts(x.astype(experts[0][0].dtype), idx, w, experts,
                          first_expert_held, body)


def check_share(first_expert_held: int, experts_held: int, total: int):
    """A module's share of a layer's ``total`` routed experts lies inside
    them."""
    if not 0 <= first_expert_held <= first_expert_held + experts_held <= total:
        raise ValueError(
            f"experts {first_expert_held}.."
            f"{first_expert_held + experts_held - 1} are not among the "
            f"router's {total}")


def held_kernels(p, names, held: int, dtype):
    """The ``experts`` ``routed_experts`` takes, from a layer's dict: every
    held expert's ``kernel`` under each of ``names`` (the order its body
    takes them), in the compute type."""
    return [tuple(p[f"experts_{j}"][name]["kernel"].astype(dtype)
                  for name in names) for j in range(held)]


def no_pick_at_pads(rule, pad_mask):
    """``rule`` with a pad position picking no expert. It lies behind the
    last token anything reads (every mixer is causal), its stream settles
    to one vector whose picks are all alike, and a held expert among them
    would get every pad position of the batch as rows: the tiles follow the
    tokens, not the draw's padding."""
    live = pad_mask.reshape(-1, 1) > 0

    def picks(router, u):
        idx, w = rule(router, u)
        # -1 is an expert held nowhere: the plan sorts such pairs behind
        # every held expert's rows, into no tile
        return jnp.where(live, idx, -1), w

    return picks
