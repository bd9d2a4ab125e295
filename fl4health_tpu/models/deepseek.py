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

The routed part is ``models/routed.py routed_layer`` with this family's
scoring rule (``route``) and expert body (``swiglu_expert``).

A family of ``decoder_common.DecoderStack``: it declares the kinds of its
layers (the leading dense ones, then the expert layers: one ``lax.scan``
each, ``[[0], [1..4]]``), their spec and ``layer``; the leaves, the forward,
the split of the parameters and ``bind_shared`` are the stack's. What a
rematerialised layer keeps: ``REMAT_KEEPS``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from fl4health_tpu.core.remat import named
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, apply_rope, lora_dense,
                                                 rms_norm, swiglu)
from fl4health_tpu.models.routed import (check_share, held_kernels,
                                         routed_gauges, routed_layer,
                                         swiglu_expert)
from fl4health_tpu.observability.stages import layer as part

# What a rematerialised layer keeps (core/remat.py): the flash calls' ``out``
# / ``lse``, per byte kept the dearest thing a layer would recompute, and
# latent attention's output stream ``h + o_proj(out)``: a frozen ``o_proj``'s
# backward reads none of its own product (the adapters' gradients read ``out``
# and the ``[T, r]`` product ``out A``), so with the stream kept the recompute
# holds no product of ``o_proj``'s kernel at all.
REMAT_KEEPS = (*common.FLASH_SAVED, common.MLA_STREAM)


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
    """(cos, sin) [T, dim // 2], float32, over YaRN's frequencies times its
    cos/sin scale (``factor`` 1: the plain tables, times exactly 1.0)."""
    return common.rope_tables(
        t, dim, rope.theta, yarn_inv_freq(dim, rope),
        yarn_get_mscale(rope.factor, rope.mscale)
        / yarn_get_mscale(rope.factor, rope.mscale_all_dim))


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


def moe(p, u, dims: DeepseekDims):
    """The routed layer's part held here plus the shared experts."""
    dt = dims.dtype
    flat = u.reshape(-1, u.shape[-1])
    with part("moe"):
        experts = held_kernels(p, ("gate_proj", "up_proj", "down_proj"),
                               dims.experts_held, dt)
        y = routed_layer(flat, flat, p["gate"], experts,
                         dims.first_expert_held,
                         lambda router, u: route(router, u, dims),
                         swiglu_expert)
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

class DeepseekV2Classifier(common.DecoderStack):
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

    # -- what the stack reads (decoder_common.DecoderStack) ------------------
    remat_keeps = REMAT_KEEPS
    float32_kernels = ("gate",)  # the router's
    block = staticmethod(layer)

    @property
    def dims(self) -> DeepseekDims:
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"{self.n_routed_experts} experts do not divide "
                             f"into {self.n_group} groups")
        check_share(self.first_expert_held, self.experts_held,
                    self.n_routed_experts)
        return DeepseekDims(
            self.d_model, self.n_heads, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.experts_held, self.first_expert_held, self.n_group,
            self.topk_group, self.top_k,
            self.routed_scaling_factor, self.rope, self.rms_eps,
            self.lora_alpha / self.lora_rank if self.lora_rank else 0.0,
            self.dtype, self.attention_fn)

    def kinds(self) -> list[bool]:
        """Is layer ``i`` routed: the leading dense layers, then the expert
        layers."""
        return [i >= self.first_k_dense for i in range(self.n_layers)]

    def spec(self, routed: bool) -> tuple:
        d, r, h = self.d_model, self.lora_rank, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        proj, norm = common.proj_spec, common.norm_spec
        mlp = functools.partial(common.swiglu_spec, d)
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
                                          self.remat_keeps)}
