"""Hybrid state-space / attention decoder for federated adapter fine-tuning
(the Jamba family: Lieber et al. 2024, arXiv:2403.19887; HF ``model_type:
jamba``).

Parity surface: /root/reference/examples/fedllm_example — LoRA adapters
trained federally over a frozen causal LM that every client loads once.

Layer ``l``: ``h <- h + Mixer_l(RMSNorm(h))`` then ``h <- h + MLP(RMSNorm(h))``.
The mixer is causal attention where ``l % attn_layer_period ==
attn_layer_offset`` (grouped-query, no positional encoding, no bias: the
state-space layers carry position) and a Mamba-1 mixer elsewhere (in_proj,
causal depthwise conv, x_proj to (delta, B, C) with Jamba's three inner
RMSNorms, dt_proj, the selective scan of kernels/selective_scan.py,
out_proj). The MLP is SwiGLU. The head is HF ``JambaForSequenceClassification``'s:
the final-norm hidden state at the last non-pad token through ``score``
(no bias); token id 0 is padding, at the tail.

TPU-native design. The parameters are an ordinary tree with stable names
(``layers_<i>/mamba/in_proj/{kernel,lora_a,lora_b}`` ...; every adapted
projection is ``W x + (alpha / r) * B^T (A^T x)`` as ``transformer.LoraDense``
has it), declared by a flax module, and the mathematics is a set of pure
functions over one layer's dict. A family of ``decoder_common.DecoderStack``:
it declares the kinds of its layers, their spec and ``layer``; the stack runs
consecutive layers of one kind as ONE ``lax.scan`` over their stacked dicts
(13 Mamba layers compile as two bodies, not thirteen), each layer
rematerialised on the backward pass under ``remat`` less ``REMAT_KEEPS``, and
brings the split of the parameters and ``bind_shared`` for the engine.
``dtype`` is the compute type at float32 parameters.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from fl4health_tpu.kernels.selective_scan import selective_scan
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, causal_depthwise_conv,
                                                 lora_dense, rms_norm, swiglu)
from fl4health_tpu.observability.stages import layer as part

# projections that carry an adapter (the PEFT recipe of AI21's model card)
ADAPTED = frozenset({"in_proj", "x_proj", "out_proj", "gate_proj", "up_proj",
                     "down_proj", "q_proj", "k_proj", "v_proj"})
# What a rematerialised layer keeps (core/remat.py): the flash calls' ``out``
# / ``lse``, per byte kept the dearest thing a layer would recompute (the
# attention layer's recompute runs no flash forward). The mixers' stream and
# the scan's residuals are not kept: thirteen layers of them want memory that
# has to be freed first (ROADMAP S9).
REMAT_KEEPS = common.FLASH_SAVED


@dataclasses.dataclass(frozen=True)
class JambaDims:
    """The sizes and static choices the layer functions read."""

    d_model: int
    d_ff: int
    n_heads: int
    n_kv_heads: int
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int
    rms_eps: float
    lora_scale: float  # alpha / rank (0 without adapters)
    dtype: Any
    attention_fn: Any


# ---------------------------------------------------------------------------
# The mathematics: pure functions over one layer's parameter dict
# ---------------------------------------------------------------------------

def mamba_mixer(p, u, dims: JambaDims):
    with part("mamba_mixer"):
        x, z = jnp.split(lora_dense(p["in_proj"], u, dims), 2, axis=-1)
        x = jax.nn.silu(causal_depthwise_conv(p["conv1d"], x)).astype(dims.dtype)
        delta, b, c = jnp.split(
            lora_dense(p["x_proj"], x, dims),
            [dims.dt_rank, dims.dt_rank + dims.d_state], axis=-1)
        delta = rms_norm(delta, p["dt_layernorm"]["scale"], dims.rms_eps)
        b = rms_norm(b, p["b_layernorm"]["scale"], dims.rms_eps)
        c = rms_norm(c, p["c_layernorm"]["scale"], dims.rms_eps)
        # the time step: softplus(W delta + bias), accumulated in float32
        dt = jax.nn.softplus(
            jnp.dot(delta.astype(dims.dtype),
                    p["dt_proj"]["kernel"].astype(dims.dtype),
                    preferred_element_type=F32) + p["dt_proj"]["bias"])
        y = selective_scan(x, dt.astype(dims.dtype),
                           -jnp.exp(p["A_log"].astype(F32)), b, c, p["D"], z)
        return lora_dense(p["out_proj"], y, dims)


def causal_attention(p, u, pad_mask, dims: JambaDims):
    """``dims.attention_fn(q, k, v, pad_mask=mask) -> out`` must be causal,
    e.g. ``functools.partial(kernels.flash_attention, causal=True, block_q=512,
    block_k=512)``; it gets ``k`` / ``v`` with one head when the model has
    one, as many as ``q`` otherwise."""
    with part("attention"):
        head_dim = dims.d_model // dims.n_heads

        def heads(name, n):
            y = lora_dense(p[name], u, dims)
            return y.reshape(*y.shape[:-1], n, head_dim)

        q = heads("q_proj", dims.n_heads)
        k, v = heads("k_proj", dims.n_kv_heads), heads("v_proj", dims.n_kv_heads)
        if 1 < dims.n_kv_heads < dims.n_heads:
            groups = dims.n_heads // dims.n_kv_heads
            k, v = (jnp.repeat(a, groups, axis=2) for a in (k, v))
        attend = dims.attention_fn or common.dense_causal_attention
        out = attend(q, k, v, pad_mask=pad_mask)
        return lora_dense(p["o_proj"], out.reshape(*out.shape[:-2], -1), dims)


def layer(p, h, pad_mask, attention: bool, dims: JambaDims):
    u = rms_norm(h, p["input_layernorm"]["scale"], dims.rms_eps)
    h = h + (causal_attention(p["self_attn"], u, pad_mask, dims) if attention
             else mamba_mixer(p["mamba"], u, dims))
    u = rms_norm(h, p["pre_ff_layernorm"]["scale"], dims.rms_eps)
    with part("mlp"):
        ff = swiglu(p["feed_forward"], u, dims)
    return h + ff


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

def _a_log_init(key, shape, dtype=F32):
    """Mamba's S4D-real start: A = -(1 .. d_state) for every channel."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _proj_spec(name: str, n_in: int, n_out: int, rank: int):
    return common.proj_spec(name, n_in, n_out, rank if name in ADAPTED else 0)


class JambaClassifier(common.DecoderStack):
    """Input: integer token ids [B, T], id 0 = padding at the tail."""

    vocab_size: int
    n_classes: int
    d_model: int = 128
    n_layers: int = 4
    d_ff: int = 256
    n_heads: int = 4
    n_kv_heads: int = 1
    mamba_expand: int = 2
    d_state: int = 16
    dt_rank: int = 8
    d_conv: int = 4
    attn_layer_period: int = 4
    attn_layer_offset: int = 2
    rms_eps: float = 1e-6
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = jnp.float32
    remat: bool = False  # rematerialise each layer on the backward pass
    attention_fn: Any = None  # causal; None = the dense form

    # -- what the stack reads (decoder_common.DecoderStack) ------------------
    final_norm = "final_layernorm"
    remat_keeps = REMAT_KEEPS
    float32_kernels = ("conv1d",)  # the conv's taps: an elementwise operand
    block = staticmethod(layer)

    @property
    def dims(self) -> JambaDims:
        return JambaDims(
            self.d_model, self.d_ff, self.n_heads, self.n_kv_heads,
            self.mamba_expand * self.d_model, self.d_state, self.dt_rank,
            self.d_conv, self.rms_eps,
            self.lora_alpha / self.lora_rank if self.lora_rank else 0.0,
            self.dtype, self.attention_fn)

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    def kinds(self) -> list[bool]:
        return [self.is_attention(i) for i in range(self.n_layers)]

    def spec(self, attention: bool) -> tuple:
        d, r = self.d_model, self.lora_rank
        norm = common.norm_spec
        if attention:
            hd = d // self.n_heads
            mixer = ("self_attn", (
                _proj_spec("q_proj", d, self.n_heads * hd, r),
                _proj_spec("k_proj", d, self.n_kv_heads * hd, r),
                _proj_spec("v_proj", d, self.n_kv_heads * hd, r),
                _proj_spec("o_proj", self.n_heads * hd, d, r)))
        else:
            di, n, rk = self.mamba_expand * d, self.d_state, self.dt_rank
            mixer = ("mamba", (
                _proj_spec("in_proj", d, 2 * di, r),
                ("conv1d", (("kernel", ((self.d_conv, di), "matrix")),
                            ("bias", ((di,), "zeros")))),
                _proj_spec("x_proj", di, rk + 2 * n, r),
                ("dt_layernorm", norm(rk)), ("b_layernorm", norm(n)),
                ("c_layernorm", norm(n)),
                ("dt_proj", (("kernel", ((rk, di), "matrix")),
                             ("bias", ((di,), "zeros")))),
                ("A_log", ((di, n), _a_log_init)), ("D", ((di,), "ones")),
                _proj_spec("out_proj", di, d, r)))
        return (("input_layernorm", norm(d)), mixer,
                ("pre_ff_layernorm", norm(d)),
                ("feed_forward", (_proj_spec("gate_proj", d, self.d_ff, r),
                                  _proj_spec("up_proj", d, self.d_ff, r),
                                  _proj_spec("down_proj", self.d_ff, d, r))))

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Which path the forward's flash calls take and what the remat
        sites keep, for the simulation's build-time gauges; ``batch_shape``
        is one client's [B, T]."""
        return common.attention_gauges(self, batch_shape, n_clients,
                                       self.remat_keeps)
