"""Hybrid Mamba-2 / attention / latent mixture-of-experts decoder for
federated adapter fine-tuning (the Nemotron-H family: NVIDIA 2025,
arXiv:2504.03624; HF ``model_type: nemotron_h``).

Parity surface: /root/reference/examples/fedllm_example — LoRA adapters
trained federally over a frozen causal LM that every client loads once.

A block is ONE mixer behind one RMSNorm and a residual add: ``h <- h +
mixer_i(RMSNorm(h))``, the kind of block ``i`` read from character ``i`` of
``pattern`` (HF ``hybrid_override_pattern``):

- ``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups, state ``N``): ``[z |
  xBC | dt] = in_proj(u)``; ``xBC <- silu(causal_depthwise_conv(xBC))``,
  split ``x [H, P]``, ``B [G, N]``, ``C [G, N]``; ``dt = softplus(dt +
  dt_bias)`` a head, ``a = -exp(A_log)`` a head; the scalar-decay recurrence
  of ``kernels/ssd_scan.py`` in its chunked form (matmuls; at widths on the
  128-lane tiles two Mosaic calls that keep the tiles on the chip), plus
  ``D x``;
  ``y <- RMSNorm_group(y * silu(z))`` (gate first, then a norm over each
  group's lanes, times a scale of ``H * P``); ``out_proj``. Causal, and pad
  positions sit at the tail, so no mask enters it.
- ``*``, attention: q / k / v / o without bias, ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads of ``head_dim``, causal, scale
  ``head_dim^-0.5``, NO positions (the state-space blocks carry them). The
  attention function gets k / v with their own ``n_kv_heads``: the flash
  calls address grouped heads where the model holds them (query head ``h``
  reads key head ``h // (n_heads / n_kv_heads)``).
- ``E``, mixture of experts in a latent: ``s = sigmoid(u W_r)`` over ALL
  ``n_routed_experts`` in float32; the ``top_k`` largest of ``s + b`` (``b``
  the selection bias: it picks, it does not weigh); ``w_k = routed_scale *
  s_k / (sum_chosen s + 1e-20)``; ``l = u W_down_latent``; ``E_j(l) =
  relu(l W_j_up)^2 W_j_down``; ``y = (sum_{k: idx_k held here} w_k
  E_{idx_k}(l)) W_up_latent + relu(u W_s_up)^2 W_s_down`` (the shared expert
  on the full width). The module is told which experts it HOLDS
  (``experts_held`` from ``first_expert_held``: a chip's share under expert
  parallelism), routes over all of them and adds its own only; the routed
  part is ``models/routed.py routed_layer`` with the sigmoid scoring rule
  (``sigmoid_route``) and this family's expert body (``relu2_expert``).

Then the final RMSNorm and HF's last-non-pad-token ``score`` head (token id
0 is padding, at the tail). Left out: the multi-token-prediction block, the
balance loss (routers are frozen), the output head.

Adapters (``lora_rank``) sit on the projections every token goes through:
``in_proj`` / ``out_proj``, q / k / v / o and the shared expert's two
matrices. The two latent projections carry none: the latent feeds and
collects the routed experts only, so their adapters' gradient would exist
only where a token picked an expert held here and would change by a whole
contribution when a near-tied pick falls the other way. Under a loss that
reads one token a sequence that is the gradient of a handful of picks: on the
v5e the bfloat16 program and a float32 reference differed by 27 % on such a
leaf's first update where every other leaf agreed to 6 % (``PERF.md``
section 6, PR 41). Routed experts, routers, norms, the conv,
``A_log`` / ``D`` / ``dt_bias`` and the embedding are frozen as well.

A family of ``decoder_common.DecoderStack``: it declares the kinds of its
blocks (the pattern's characters), their spec and ``block``; the leaves, the
forward, the split of the parameters and ``bind_shared`` are the stack's. The
published pattern alternates, so a run of LIKE blocks would be one block
long; what repeats is a unit (``ME ME ME``): at ``MAX_UNIT`` 2 the stack's
``runs()`` cuts the pattern into units of one or two blocks that repeat, and
each run is one ``lax.scan`` over its stacked units, every block
rematerialised on its own under ``remat`` less ``REMAT_KEEPS``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from fl4health_tpu.kernels.ssd_scan import (count_call_sites, n_chunks,
                                            ssd_scan)
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, causal_depthwise_conv,
                                                 lora_dense, rms_norm)
from fl4health_tpu.models.routed import (check_share, held_kernels,
                                         no_pick_at_pads, relu2_expert,
                                         routed_gauges, routed_layer,
                                         sigmoid_route)
from fl4health_tpu.observability.stages import layer as part

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the longest unit of unlike blocks that ``runs()`` looks for a repeat of
MAX_UNIT = 2
# What a rematerialised block keeps (core/remat.py). A block is one mixer:
# the attention block keeps the flash calls' pair; a Mamba-2 or an expert
# block keeps nothing (the chunked scan's chunk states are 4 MB a sequence
# and chunk, its decay tiles far more)
REMAT_KEEPS = common.FLASH_SAVED


@dataclasses.dataclass(frozen=True)
class NemotronHDims:
    """The sizes and static choices the block functions read."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    chunk: int
    experts_held: int
    first_expert_held: int
    top_k: int
    routed_scale: float
    rms_eps: float
    lora_scale: float  # alpha / rank (0 without adapters)
    dtype: Any
    attention_fn: Any


# ---------------------------------------------------------------------------
# The mathematics: pure functions over one block's parameter dict
# ---------------------------------------------------------------------------

def ssd_mixer(p, u, dims: NemotronHDims):
    with part("ssd_mixer"):
        h, hp = dims.ssm_heads, dims.ssm_head_dim
        d_inner, gn = h * hp, dims.ssm_groups * dims.ssm_state
        z, xbc, dt = jnp.split(lora_dense(p["in_proj"], u, dims),
                               [d_inner, 2 * d_inner + 2 * gn], axis=-1)
        xbc = jax.nn.silu(causal_depthwise_conv(p["conv1d"], xbc)).astype(
            dims.dtype)
        x, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
        x = x.reshape(*x.shape[:-1], h, hp)
        b, c = (v.reshape(*v.shape[:-1], dims.ssm_groups, dims.ssm_state)
                for v in (b, c))
        # the time step and the decay, a head each, in float32
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        y = ssd_scan(x, dt, -jnp.exp(p["A_log"].astype(F32)), b, c,
                     dims.chunk)
        y = y + p["D"].astype(F32)[:, None] * x.astype(F32)
        # gate first, then the norm over each group's lanes
        y = y.reshape(*y.shape[:-2], dims.ssm_groups, -1) * jax.nn.silu(
            z.astype(F32)).reshape(*z.shape[:-1], dims.ssm_groups, -1)
        y = rms_norm(y, p["norm"]["scale"].reshape(dims.ssm_groups, -1),
                     dims.rms_eps)
        return lora_dense(p["out_proj"], y.reshape(*y.shape[:-2], d_inner),
                          dims)


def gqa_attention(p, u, pad_mask, dims: NemotronHDims):
    """``dims.attention_fn(q, k, v, pad_mask=mask) -> out`` must be causal
    and take k / v with FEWER heads than q, each serving ``n_heads /
    n_kv_heads`` consecutive query heads (``kernels.flash_attention``
    does); ``None`` is the dense form over the repeated heads."""
    with part("attention"):
        def heads(name, n):
            y = lora_dense(p[name], u, dims)
            return y.reshape(*y.shape[:-1], n, dims.head_dim)

        q = heads("q_proj", dims.n_heads)
        k, v = heads("k_proj", dims.n_kv_heads), heads("v_proj", dims.n_kv_heads)
        with part("gqa_flash"):
            if dims.attention_fn is None:
                rep = dims.n_heads // dims.n_kv_heads
                out = common.dense_causal_attention(
                    q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                    pad_mask)
            else:
                out = dims.attention_fn(q, k, v, pad_mask=pad_mask)
        return lora_dense(p["o_proj"], out.reshape(*out.shape[:-2], -1), dims)


def relu2_mlp(p, u, dims: NemotronHDims):
    return lora_dense(p["down_proj"], jnp.square(jax.nn.relu(
        lora_dense(p["up_proj"], u, dims))), dims)


def latent_moe(p, u, pad_mask, dims: NemotronHDims):
    """The routed layer's part held here, through the latent, plus the
    shared expert. A pad position picks no expert
    (``routed.no_pick_at_pads``)."""
    flat = u.reshape(-1, u.shape[-1])
    rule = no_pick_at_pads(
        lambda router, x: sigmoid_route(router, x, dims.top_k,
                                        dims.routed_scale), pad_mask)
    with part("moe"):
        with part("moe_latent"):
            latent = lora_dense(p["fc1_latent_proj"], flat, dims)
        experts = held_kernels(p, ("up_proj", "down_proj"),
                               dims.experts_held, dims.dtype)
        y = routed_layer(
            latent, flat, p["gate"], experts, dims.first_expert_held, rule,
            relu2_expert)
        with part("moe_latent"):
            y = lora_dense(p["fc2_latent_proj"], y, dims)
    with part("shared_experts"):
        shared = relu2_mlp(p["shared_experts"], u, dims)
    return y.reshape(shared.shape) + shared


def block(p, h, pad_mask, kind: str, dims: NemotronHDims):
    u = rms_norm(h, p["norm"]["scale"], dims.rms_eps)
    if kind == MAMBA:
        return h + ssd_mixer(p["mixer"], u, dims)
    if kind == ATTENTION:
        return h + gqa_attention(p["mixer"], u, pad_mask, dims)
    return h + latent_moe(p["mixer"], u, pad_mask, dims)


# ---------------------------------------------------------------------------
# The module
# ---------------------------------------------------------------------------

class NemotronHClassifier(common.DecoderStack):
    """Input: integer token ids [B, T], id 0 = padding at the tail."""

    vocab_size: int
    n_classes: int
    pattern: str = "ME*E"
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_groups: int = 2
    ssm_state: int = 16
    d_conv: int = 4
    chunk: int = 128
    n_routed_experts: int = 16  # the router's width
    experts_held: int = 16
    first_expert_held: int = 0
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    d_latent: int = 32
    d_expert: int = 48  # one routed expert, on the latent
    d_shared: int = 96  # the shared expert, on the full width
    rms_eps: float = 1e-5
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = jnp.float32
    remat: bool = False  # rematerialise each block on the backward pass
    attention_fn: Any = None  # causal, grouped key/value heads; None = dense

    # -- what the stack reads (decoder_common.DecoderStack) ------------------
    max_unit = MAX_UNIT
    final_norm = "norm_f"
    remat_keeps = REMAT_KEEPS
    float32_kernels = ("gate", "conv1d")  # the router's, the conv's taps
    block = staticmethod(block)

    @property
    def dims(self) -> NemotronHDims:
        if set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"pattern {self.pattern!r}: a block is one of "
                             f"{MAMBA} {EXPERTS} {ATTENTION}")
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError("query heads divide into key/value heads and "
                             "state-space heads into groups")
        check_share(self.first_expert_held, self.experts_held,
                    self.n_routed_experts)
        return NemotronHDims(
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim,
            self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
            self.ssm_state, self.chunk, self.experts_held,
            self.first_expert_held, self.top_k, self.routed_scaling_factor,
            self.rms_eps,
            self.lora_alpha / self.lora_rank if self.lora_rank else 0.0,
            self.dtype, self.attention_fn)

    def kinds(self) -> str:
        return self.pattern

    def spec(self, kind: str) -> tuple:
        d, r = self.d_model, self.lora_rank
        proj, norm = common.proj_spec, common.norm_spec
        if kind == MAMBA:
            d_inner = self.ssm_heads * self.ssm_head_dim
            conv = d_inner + 2 * self.ssm_groups * self.ssm_state
            mixer = (
                proj("in_proj", d, d_inner + conv + self.ssm_heads, r),
                ("conv1d", (("kernel", ((self.d_conv, conv), "matrix")),
                            ("bias", ((conv,), "zeros")))),
                ("dt_bias", ((self.ssm_heads,), "zeros")),
                ("A_log", ((self.ssm_heads,), "zeros")),
                ("D", ((self.ssm_heads,), "ones")),
                ("norm", norm(d_inner)),
                proj("out_proj", d_inner, d, r))
        elif kind == ATTENTION:
            mixer = (proj("q_proj", d, self.n_heads * self.head_dim, r),
                     proj("k_proj", d, self.n_kv_heads * self.head_dim, r),
                     proj("v_proj", d, self.n_kv_heads * self.head_dim, r),
                     proj("o_proj", self.n_heads * self.head_dim, d, r))
        else:
            # routed experts and the router are frozen and unadapted; every
            # expert's leaves have names of their own
            mixer = (
                ("gate", (("kernel", ((d, self.n_routed_experts), "matrix")),
                          ("e_score_correction_bias",
                           ((self.n_routed_experts,), "zeros")))),
                # the latent is read and written by the routed experts alone,
                # so an adapter on either projection would take its gradient
                # through a token's discrete picks (module docstring): none
                proj("fc1_latent_proj", d, self.d_latent, 0),
                proj("fc2_latent_proj", self.d_latent, d, 0),
                *((f"experts_{j}", (
                    proj("up_proj", self.d_latent, self.d_expert, 0),
                    proj("down_proj", self.d_expert, self.d_latent, 0)))
                  for j in range(self.experts_held)),
                ("shared_experts", (proj("up_proj", d, self.d_shared, r),
                                    proj("down_proj", self.d_shared, d, r))))
        return ("norm", norm(d)), ("mixer", mixer)

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Static facts of the state-space and routed blocks, which path the
        forward's flash calls and chunked scans take (``ssd_calls_fused`` /
        ``ssd_calls_xla``: traced call sites) and what the remat sites keep,
        for the simulation's build-time gauges; ``batch_shape`` is one
        client's [B, T]."""
        return {"ssd_chunks": n_chunks(batch_shape[-1], self.chunk),
                "ssd_heads": self.ssm_heads,
                "moe_experts_held": self.experts_held,
                "moe_router_width": self.n_routed_experts,
                "moe_top_k": self.top_k,
                **routed_gauges(n_clients * math.prod(batch_shape),
                                self.top_k, self.experts_held,
                                self.n_routed_experts),
                **common.attention_gauges(self, batch_shape, n_clients,
                                          self.remat_keeps,
                                          ssd_calls=count_call_sites)}
