"""Sliding-window / full attention mixture-of-experts decoder for federated
adapter fine-tuning (the AFMoE family: Arcee Trinity, HF ``model_type:
afmoe``; the equations are ``config.json``'s keys, in brackets, and where
the config is silent HF ``transformers``' ``modeling_afmoe.py``, marked
[modeling]).

Parity surface: /root/reference/examples/fedllm_example — LoRA adapters
trained federally over a frozen causal LM that every client loads once.

Embedding: ``h0 = E[x] * sqrt(d_model)`` (``mup_enabled``; the one form built).

Layer ``l``, of kind ``layer_types[l]`` (``sliding_attention`` or
``full_attention``), four RMSNorms, the sandwich form [modeling]:
``h <- h + post_attention_layernorm(Attn_l(input_layernorm(h)))`` then
``h <- h + post_mlp_layernorm(FF_l(pre_mlp_layernorm(h)))``.

``Attn_l(u)``: ``q = u W_q`` as ``n_heads`` heads of ``head_dim``, ``k = u
W_k``, ``v = u W_v`` as ``n_kv_heads`` heads, ``g = u W_g`` [modeling:
``gate_proj``], none with a bias; ``q <- RMSNorm(q)``, ``k <- RMSNorm(k)``
over each head's lanes with learned scales ``q_norm`` / ``k_norm``
[modeling]; on a SLIDING layer only, rotary positions over the whole head
(``rope_theta``, no scaling, the halves layout of ``apply_rope``); a
FULL layer applies no positions [modeling]. Scores ``q k^T / sqrt(head_dim)``,
query head h over key head ``h // (n_heads / n_kv_heads)``; query i sees key
j iff ``j <= i``, j is no pad, and on a sliding layer ``i - j <
sliding_window`` (itself and the ``sliding_window - 1`` before it); softmax
in float32; ``o = (P v) * sigmoid(g)``; ``o W_o``.

``FF_l`` for ``l < num_dense_layers``: SwiGLU of width ``d_ff``. Otherwise
``s = sigmoid(u W_r)`` over ALL ``n_routed_experts`` in float32
(``score_func``); the ``top_k`` largest of ``s + expert_bias`` are chosen
(the bias picks, it does not weigh; no group limit), weights ``route_scale *
s_e / (sum_chosen s + 1e-20)`` (``route_norm``); each expert a SwiGLU of
width ``d_expert``; plus ONE shared SwiGLU of ``n_shared_experts *
d_expert`` on every token. The module is told which experts it HOLDS
(``experts_held`` from ``first_expert_held``: a chip's share under expert
parallelism), routes over all of them and adds its own only: the routed part
is ``models/routed.py routed_layer`` with ``sigmoid_route`` (the rule
``models/nemotron_h.py`` has, at other numbers) and ``swiglu_expert``.
A pad position picks no expert (``routed.no_pick_at_pads``).
``load_balance_coeff`` is the training recipe's bias update: unused (router
and bias are frozen).

Then the final RMSNorm and HF's last-non-pad-token ``score`` head (token id
0 is padding, at the tail). Left out: the output head.

Adapters (``lora_rank``) sit on the projections every token goes through:
q / k / v / o, the attention ``gate_proj``, the dense layers' and the shared
expert's three matrices; ``score`` trains. Routed experts, the router,
``expert_bias``, every norm (``q_norm`` / ``k_norm`` too) and the embedding
are frozen and unadapted: an adapter inside the routed path takes its
gradient through a token's discrete picks (``models/nemotron_h.py``).

A family of ``decoder_common.DecoderStack``: it declares the kinds of its
layers (kind of attention AND of feed-forward), their spec and ``layer``; the
leaves, the forward, the split of the parameters and ``bind_shared`` are the
stack's. Layers alike in kind that follow one another are one ``lax.scan``
(``runs()``: two periods of the published pattern with two leading dense
layers are ``[SS] [S] [F] [SSS] [F]``), every layer rematerialised on its own
under ``remat`` less ``REMAT_KEEPS``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, apply_rope, lora_dense,
                                                 rms_norm, rope_tables,
                                                 swiglu)
from fl4health_tpu.models.routed import (check_share, held_kernels,
                                         no_pick_at_pads, routed_gauges,
                                         routed_layer, sigmoid_route,
                                         swiglu_expert)
from fl4health_tpu.observability.stages import layer as part

SLIDING, FULL = "sliding_attention", "full_attention"
# what a rematerialised layer keeps (core/remat.py): the flash calls' ``out``
# / ``lse``, window and full attention alike
REMAT_KEEPS = common.FLASH_SAVED


@dataclasses.dataclass(frozen=True)
class AfmoeDims:
    """The sizes and static choices the layer functions read."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    sliding_window: int
    rope_theta: float
    experts_held: int
    first_expert_held: int
    top_k: int
    route_scale: float
    rms_eps: float
    lora_scale: float  # alpha / rank (0 without adapters)
    dtype: Any
    attention_fn: Any


# ---------------------------------------------------------------------------
# The mathematics: pure functions over one layer's parameter dict
# ---------------------------------------------------------------------------

def gated_attention(p, u, pad_mask, window, rope, dims: AfmoeDims):
    """``window``: None on a full layer, the number of positions a query
    sees on a sliding one; ``rope``: None, or the (cos, sin) tables over the
    whole head. ``dims.attention_fn(q, k, v, pad_mask=mask, window=w) ->
    out`` must be causal, take k / v with FEWER heads than q (each serving
    ``n_heads / n_kv_heads`` consecutive query heads) and a static
    ``window`` (``kernels.flash_attention`` does); ``None`` is the dense
    form over the repeated heads."""
    with part("attention"):
        def heads(name, n):
            y = lora_dense(p[name], u, dims)
            return y.reshape(*y.shape[:-1], n, dims.head_dim)

        q = heads("q_proj", dims.n_heads)
        k, v = heads("k_proj", dims.n_kv_heads), heads("v_proj", dims.n_kv_heads)
        gate = lora_dense(p["gate_proj"], u, dims)
        # a norm over each head's lanes, then (sliding layers) the positions,
        # both in float32; the flash calls get the compute type
        q = rms_norm(q, p["q_norm"]["scale"], dims.rms_eps)
        k = rms_norm(k, p["k_norm"]["scale"], dims.rms_eps)
        if rope is not None:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        q, k = q.astype(dims.dtype), k.astype(dims.dtype)
        # a full layer's calls under the name the grouped calls have
        # elsewhere, a sliding layer's under their own
        with part("gqa_flash") if window is None else part("window_flash"):
            if dims.attention_fn is None:
                rep = dims.n_heads // dims.n_kv_heads
                out = common.dense_causal_attention(
                    q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                    pad_mask, window=window)
            else:
                out = dims.attention_fn(q, k, v, pad_mask=pad_mask,
                                        window=window)
        # the gate, in float32. No scope of its own: on the v5e XLA fuses
        # the sigmoid and the product into the projections' fusions, so a
        # scope here named no op of a trace (PR 43's traced run)
        out = (out.reshape(*out.shape[:-2], -1).astype(F32)
               * jax.nn.sigmoid(gate.astype(F32))).astype(dims.dtype)
        return lora_dense(p["o_proj"], out, dims)


def moe(p, u, pad_mask, dims: AfmoeDims):
    """The routed layer's part held here plus the shared expert. A pad
    position picks no expert (``routed.no_pick_at_pads``)."""
    dt = dims.dtype
    flat = u.reshape(-1, u.shape[-1])
    rule = no_pick_at_pads(
        lambda router, x: sigmoid_route(router, x, dims.top_k,
                                        dims.route_scale), pad_mask)
    with part("moe"):
        experts = held_kernels(p, ("gate_proj", "up_proj", "down_proj"),
                               dims.experts_held, dt)
        y = routed_layer(
            flat, flat,
            {"kernel": p["router"]["kernel"],
             "e_score_correction_bias": p["expert_bias"]},
            experts, dims.first_expert_held, rule, swiglu_expert)
    with part("shared_experts"):
        shared = swiglu(p["shared_experts"], u, dims)
    return y.reshape(u.shape).astype(dt) + shared


def layer(p, h, pad_mask, kind: str, routed: bool, dims: AfmoeDims):
    eps, dt = dims.rms_eps, dims.dtype
    sliding = kind == SLIDING
    rope = (rope_tables(h.shape[1], dims.head_dim, dims.rope_theta)
            if sliding else None)
    u = rms_norm(h, p["input_layernorm"]["scale"], eps)
    a = gated_attention(p["self_attn"], u, pad_mask,
                        dims.sliding_window if sliding else None, rope, dims)
    h = h + rms_norm(a, p["post_attention_layernorm"]["scale"], eps).astype(dt)
    u = rms_norm(h, p["pre_mlp_layernorm"]["scale"], eps)
    if routed:
        ff = moe(p["mlp"], u, pad_mask, dims)
    else:
        with part("mlp"):
            ff = swiglu(p["mlp"], u, dims)
    return h + rms_norm(ff, p["post_mlp_layernorm"]["scale"], eps).astype(dt)


# ---------------------------------------------------------------------------
# The module
# ---------------------------------------------------------------------------

class AfmoeClassifier(common.DecoderStack):
    """Input: integer token ids [B, T], id 0 = padding at the tail."""

    vocab_size: int
    n_classes: int
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    num_dense_layers: int = 1
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 96  # the leading dense layers' SwiGLU
    d_expert: int = 32  # one routed expert's SwiGLU
    n_routed_experts: int = 16  # the router's width
    experts_held: int = 16
    first_expert_held: int = 0
    n_shared_experts: int = 1  # one SwiGLU of n_shared_experts * d_expert
    top_k: int = 4
    route_scale: float = 1.0
    sliding_window: int = 8
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = jnp.float32
    remat: bool = False  # rematerialise each layer on the backward pass
    attention_fn: Any = None  # causal, grouped heads, ``window``; None = dense

    # -- what the stack reads (decoder_common.DecoderStack) ------------------
    remat_keeps = REMAT_KEEPS
    float32_kernels = ("router",)

    @staticmethod
    def block(p, h, pad_mask, kind: tuple, dims: AfmoeDims):
        return layer(p, h, pad_mask, *kind, dims)

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.d_model)

    @property
    def dims(self) -> AfmoeDims:
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}: a layer is "
                             f"{SLIDING!r} or {FULL!r}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("query heads divide into key/value heads and "
                             "a head into two halves")
        check_share(self.first_expert_held, self.experts_held,
                    self.n_routed_experts)
        return AfmoeDims(
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim,
            self.sliding_window, self.rope_theta, self.experts_held,
            self.first_expert_held, self.top_k, self.route_scale,
            self.rms_eps,
            self.lora_alpha / self.lora_rank if self.lora_rank else 0.0,
            self.dtype, self.attention_fn)

    def routed(self, i: int) -> bool:
        return i >= self.num_dense_layers

    def kinds(self) -> list[tuple[str, bool]]:
        """(kind of attention, is the feed-forward routed) of each layer."""
        return [(kind, self.routed(i))
                for i, kind in enumerate(self.layer_types)]

    def spec(self, kind: tuple) -> tuple:
        routed = kind[1]
        d, r, hd = self.d_model, self.lora_rank, self.head_dim
        proj, norm = common.proj_spec, common.norm_spec
        mlp = functools.partial(common.swiglu_spec, d)
        attn = ("self_attn", (
            proj("q_proj", d, self.n_heads * hd, r),
            proj("k_proj", d, self.n_kv_heads * hd, r),
            proj("v_proj", d, self.n_kv_heads * hd, r),
            proj("gate_proj", d, self.n_heads * hd, r),
            ("q_norm", norm(hd)), ("k_norm", norm(hd)),
            proj("o_proj", self.n_heads * hd, d, r)))
        if routed:
            # routed experts, the router and its selection bias are frozen
            # and unadapted; every expert's leaves have names of their own
            ffn = (("router", (("kernel", ((d, self.n_routed_experts),
                                           "matrix")),)),
                   ("expert_bias", ((self.n_routed_experts,), "zeros")),
                   *((f"experts_{j}", mlp(self.d_expert, 0))
                     for j in range(self.experts_held)),
                   ("shared_experts",
                    mlp(self.n_shared_experts * self.d_expert, r)))
        else:
            ffn = mlp(self.d_ff, r)
        return (("input_layernorm", norm(d)), attn,
                ("post_attention_layernorm", norm(d)),
                ("pre_mlp_layernorm", norm(d)), ("mlp", ffn),
                ("post_mlp_layernorm", norm(d)))

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Static facts of the attention mix and the routed layer, which
        path the forward's flash calls take, how many of the traced calls
        run under the window and how many tiles a head of one executes
        beside what ``causal`` alone would, how a head of the newest causal
        call walks its live range (``flash_tiles_edge`` / ``_interior``,
        ``flash_cond_steps``), and what the remat sites keep,
        for the simulation's build-time gauges; ``batch_shape`` is one
        client's [B, T]."""
        gauges = common.attention_gauges(self, batch_shape, n_clients,
                                         self.remat_keeps)
        under_window = gauges.pop("flash_calls_window", 0)
        traced = gauges["flash_calls_lane_indexed"] + gauges[
            "flash_calls_transposed"]
        return {"flash_calls_window": under_window,
                "flash_calls_full": traced - under_window,
                "flash_window": self.sliding_window,
                "flash_window_tiles_live": gauges.pop(
                    "flash_calls_window_tiles_live", 0),
                "flash_window_tiles_causal": gauges.pop(
                    "flash_calls_window_tiles_causal", 0),
                "moe_experts_held": self.experts_held,
                "moe_router_width": self.n_routed_experts,
                "moe_top_k": self.top_k,
                **routed_gauges(n_clients * math.prod(batch_shape),
                                self.top_k, self.experts_held,
                                self.n_routed_experts),
                **gauges}
