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
functions over one layer's dict. Consecutive layers of one kind run as ONE
``lax.scan`` over their stacked dicts (13 Mamba layers compile as two bodies,
not thirteen), each layer rematerialised on the backward pass under
``remat``, less the flash calls' ``out`` / ``lse``, which are kept
(``decoder_common.JAMBA_REMAT_KEEPS``): the attention layer's recompute runs
no flash forward. ``dtype`` is the compute type at float32 parameters.

The module brings the split of its parameters with it
(``per_client_param``: adapters and head per client, the base shared) and the
forward over the two halves (``bind_shared``: the base's matrices cast to
``dtype`` and stacked over each run of layers once a round, under the
``fl_layer::shared_cast`` scope, then the forward over a client's own
leaves), which ``clients/engine.from_flax`` hands to the engine: the base
then exists once on the device however many clients train adapters over it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fl4health_tpu.core.pytree import merge_trees
from fl4health_tpu.kernels.selective_scan import selective_scan
from fl4health_tpu.models import decoder_common as common
from fl4health_tpu.models.decoder_common import (F32, lora_dense, rms_norm,
                                                 swiglu)
from fl4health_tpu.observability.stages import layer as part

# projections that carry an adapter (the PEFT recipe of AI21's model card)
ADAPTED = frozenset({"in_proj", "x_proj", "out_proj", "gate_proj", "up_proj",
                     "down_proj", "q_proj", "k_proj", "v_proj"})


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

def causal_depthwise_conv(p, x):
    """y_t = sum_j kernel[j] * x_{t - (K - 1) + j} + bias per channel (HF
    ``conv1d.weight[c, 0, j]`` is ``kernel[j, c]``), float32."""
    width, t = p["kernel"].shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * p["kernel"][j] for j in range(width)) + p["bias"]


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


class JambaClassifier(nn.Module):
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

    # -- structure ----------------------------------------------------------
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

    def runs(self) -> list[list[int]]:
        """Consecutive layers of one kind: [[0..6], [7], [8..13]]."""
        out: list[list[int]] = []
        for i in range(self.n_layers):
            if out and self.is_attention(out[-1][0]) == self.is_attention(i):
                out[-1].append(i)
            else:
                out.append([i])
        return out

    def _layer_spec(self, attention: bool) -> tuple:
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

    # -- forward ------------------------------------------------------------
    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # no dropout, no batch statistics
        d = self.d_model
        spec = [("embed_tokens", (("embedding", ((self.vocab_size, d),
                                                 "embed")),)),
                ("final_layernorm", common.norm_spec(d)),
                ("score", (("kernel", ((d, self.n_classes), "matrix")),))]
        spec += [(f"layers_{i}", self._layer_spec(self.is_attention(i)))
                 for i in range(self.n_layers)]
        params = {name: common.Leaves(entry, name=name)()
                  for name, entry in spec}
        return self.forward(self.stack_runs(params), x)

    def stack_runs(self, tree):
        return common.stack_runs(tree, self.runs())

    def forward(self, stacked, x):
        """``stacked``: the tree with its layers stacked by ``stack_runs``.
        Each run of layers is one ``lax.scan`` over its stack. (One scan over
        all the Mamba layers with the attention layer under a ``lax.cond``
        would compile one body fewer, but XLA then plans 13.1 GB of
        temporaries for the round where this form takes 9.9: PR 27.)"""
        dims = self.dims
        pad_mask = (x > 0).astype(F32)
        h = common.embed_tokens(stacked["embed_tokens"]["embedding"], x,
                                self.dtype)
        for k, run in enumerate(self.runs()):
            attention = self.is_attention(run[0])

            def body(h_, p, attention=attention):
                return layer(p, h_, pad_mask, attention, dims).astype(
                    self.dtype), None

            body = common.remat_layers(body, self.remat,
                                       common.JAMBA_REMAT_KEEPS)
            h, _ = jax.lax.scan(body, h, stacked["runs"][str(k)])
        return common.last_token_logits(
            h, pad_mask, stacked["final_layernorm"]["scale"],
            stacked["score"]["kernel"], self.rms_eps)

    # -- the split of the parameters (clients/engine.py ModelDef) ----------
    def per_client_param(self, path: str) -> bool:
        return common.PER_CLIENT(path)

    def prepare_shared(self, shared):
        """The base in the form every client step of a round consumes: each
        projection's ``kernel`` in the compute type (the conv's taps, the
        norms, ``A_log``, ``D`` and the embedding stay float32: elementwise
        operands and a gather), the layers stacked over their runs, each
        cast writing its slice of the stack."""
        return common.prepare_shared(
            shared, self.runs(), self.dtype,
            lambda names: names[-1] == "kernel" and "conv1d" not in names)

    def bind_shared(self, shared):
        """``(per_client, x) -> (preds, features)`` over a base prepared
        here, once: the client's own leaves are stacked at each call (they
        are small)."""
        with part("shared_cast"):
            prepared = self.prepare_shared(shared)
        return lambda per_client, x: self.forward(
            merge_trees(prepared, self.stack_runs(per_client)), x)

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Which path the forward's flash calls take and what the remat
        sites keep, for the simulation's build-time gauges; ``batch_shape``
        is one client's [B, T]."""
        return common.attention_gauges(self, batch_shape, n_clients,
                                       common.JAMBA_REMAT_KEEPS)
