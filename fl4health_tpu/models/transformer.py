"""Transformer encoder for federated sequence classification (BERT-class).

Parity surface: the reference's BERT fine-tuning capability
(/root/reference/examples/bert_finetuning_example — HF
``BertForSequenceClassification`` trained under BasicClient;
/root/reference/research/ag_news — dynamic-layer/sparse exchange on BERT;
/root/reference/examples/fedllm_example — LoRA fine-tuning via peft).

TPU-native design: a from-scratch flax encoder whose matmuls are shaped for
the MXU (d_model/d_ff multiples of 128 by default) with a ``dtype`` knob for
bf16 compute at fp32 params (the TPU mixed-precision recipe — no GradScaler
needed). Projection modules carry stable names (q_proj/k_proj/v_proj/o_proj,
ff_in/ff_out) so tensor-parallel sharding rules (parallel/tp.py) and
LoRA/PEFT path filters (utils/peft.py) can key on paths instead of module
classes. LoRA lives in ``LoraDense``: frozen-by-mask base kernel + low-rank
``lora_a @ lora_b`` delta, the pytree equivalent of peft's adapter injection
(/root/reference/fl4health/utils/peft_parameter_extraction.py:7).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.kernels.flash_attention import (SAVED_NAMES as FLASH_SAVED,
                                                   count_call_sites)
from fl4health_tpu.observability.stages import layer as part

# What a rematerialised encoder block keeps (core/remat.py): the flash
# calls' ``out`` / ``lse``, the dearest thing a block would recompute per
# byte kept. With the dense core, or an ``attention_fn`` that names nothing,
# the list names nothing and the whole block is recomputed.
REMAT_KEEPS = FLASH_SAVED


def _master_as(param, dtype):
    """A float32 master in the compute type: for a module with a ``dtype`` of
    its own, what ``precision.policy.cast_model_def`` does for the others."""
    with part("param_cast"):
        return param.astype(dtype)


class LoraDense(nn.Module):
    """Dense with an additive low-rank adapter: y = xW + s * (x A) B.

    ``lora_b`` initializes to zero so the adapted model starts exactly at the
    base model (the published LoRA recipe). The base kernel/bias stay in the
    params tree (frozen via the optimizer mask, utils/peft.py) so the SAME
    pytree serves full fine-tuning and PEFT — only the mask and the
    exchanger's path filter change.
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (in_features, self.features)
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        y = (x.astype(self.dtype) @ _master_as(kernel, self.dtype)
             + _master_as(bias, self.dtype))
        if self.rank > 0:
            lora_a = self.param(
                "lora_a",
                nn.initializers.normal(stddev=1.0 / self.rank),
                (in_features, self.rank),
            )
            lora_b = self.param(
                "lora_b", nn.initializers.zeros, (self.rank, self.features)
            )
            scale = self.alpha / self.rank
            with part("lora"):
                delta = scale * (
                    (x.astype(self.dtype) @ lora_a.astype(self.dtype))
                    @ lora_b.astype(self.dtype)
                )
            y = y + delta
        return y


class MultiHeadSelfAttention(nn.Module):
    """``attention_fn`` swaps the score/softmax/value core for an alternative
    implementation called as ``attention_fn(q, k, v, pad_mask=mask) -> out``
    (q/k/v/out all [B, T, H, D]) — e.g.
    ``functools.partial(parallel.ring_attention.ring_self_attention, mesh=m)``
    for long-context sequence parallelism over a (seq,) mesh. Attention
    dropout only applies to the default dense core (ring attention streams
    blocks and never materializes the score matrix).
    """

    d_model: int
    n_heads: int
    lora_rank: int = 0
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    attention_fn: Any = None

    @nn.compact
    def __call__(self, x, pad_mask, train: bool):
        # x: [B, T, D]; pad_mask: [B, T] 1=token, 0=pad
        assert self.d_model % self.n_heads == 0, (
            f"d_model={self.d_model} must divide by n_heads={self.n_heads}"
        )
        head_dim = self.d_model // self.n_heads
        with part("attention"):
            dense = lambda name: LoraDense(  # noqa: E731
                self.d_model, rank=self.lora_rank, dtype=self.dtype, name=name
            )
            q = dense("q_proj")(x)
            k = dense("k_proj")(x)
            v = dense("v_proj")(x)

            def split(t):
                return t.reshape(*t.shape[:-1], self.n_heads, head_dim)

            q, k, v = split(q), split(k), split(v)
            if self.attention_fn is not None:
                out = self.attention_fn(q, k, v, pad_mask=pad_mask)
            else:
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                    jnp.asarray(head_dim, self.dtype)
                )
                neg = jnp.asarray(jnp.finfo(jnp.float32).min, scores.dtype)
                scores = jnp.where(pad_mask[:, None, None, :] > 0, scores, neg)
                attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
                    self.dtype
                )
                if train and self.dropout_rate > 0:
                    attn = nn.Dropout(self.dropout_rate, deterministic=False)(attn)
                out = jnp.einsum("bhqk,bkhd->bqhd", attn, v)
            out = out.reshape(*out.shape[:-2], self.d_model)
            return dense("o_proj")(out)


class EncoderBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    lora_rank: int = 0
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    attention_fn: Any = None

    @nn.compact
    def __call__(self, x, pad_mask, train: bool):
        # Pre-LN (stable at small scale, standard for from-scratch training).
        with part("norm"):
            h = nn.LayerNorm(name="ln_attn")(x)
        h = MultiHeadSelfAttention(
            self.d_model, self.n_heads, self.lora_rank, self.dtype,
            self.dropout_rate, self.attention_fn, name="attn",
        )(h, pad_mask, train)
        if train and self.dropout_rate > 0:
            h = nn.Dropout(self.dropout_rate, deterministic=False)(h)
        x = x + h
        with part("norm"):
            h = nn.LayerNorm(name="ln_mlp")(x)
        with part("mlp"):
            h = LoraDense(self.d_ff, rank=self.lora_rank, dtype=self.dtype,
                          name="ff_in")(h)
            h = nn.gelu(h)
            h = LoraDense(
                self.d_model, rank=self.lora_rank, dtype=self.dtype, name="ff_out"
            )(h)
        if train and self.dropout_rate > 0:
            h = nn.Dropout(self.dropout_rate, deterministic=False)(h)
        return x + h


class TransformerClassifier(nn.Module):
    """Encoder + mean-pool + classifier head, the AG-News/BERT-shaped model.

    Input: integer token ids [B, T]; id 0 is the pad token (mask derived
    in-model, so the engine's (x, y) batch contract holds unchanged).
    """

    vocab_size: int
    n_classes: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_len: int = 128
    lora_rank: int = 0
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    attention_fn: Any = None  # e.g. ring attention for long contexts
    remat: bool = False  # rematerialize each encoder block on the backward
    # pass: activation memory drops from O(n_layers * T * d_model) to one
    # layer's worth plus what ``REMAT_KEEPS`` names, at the cost of a second
    # forward less the flash kernel's (its ``out`` / ``lse`` are kept) — the
    # standard TPU HBM-for-FLOPs trade for big-model configs (jax.checkpoint).

    @nn.compact
    def __call__(self, x, train: bool = True):
        pad_mask = (x > 0).astype(jnp.float32)
        with part("embed"):
            tok = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(x)
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(stddev=0.02),
                (self.max_len, self.d_model),
            )
            h = (tok + pos[None, : x.shape[1]]).astype(self.dtype)
        # static_argnums counts the module itself: (self, h, pad_mask, train)
        block_cls = nn.remat(
            EncoderBlock, static_argnums=(3,),
            policy=remat_names.keep(REMAT_KEEPS)) if self.remat else EncoderBlock
        for i in range(self.n_layers):
            h = block_cls(
                self.d_model, self.n_heads, self.d_ff, self.lora_rank,
                self.dtype, self.dropout_rate, self.attention_fn,
                name=f"layer_{i}",
            )(h, pad_mask, train)
        with part("norm"):
            h = nn.LayerNorm(name="ln_final")(h.astype(jnp.float32))
        with part("head"):
            denom = jnp.maximum(pad_mask.sum(axis=1, keepdims=True), 1.0)
            pooled = (h * pad_mask[..., None]).sum(axis=1) / denom
            logits = nn.Dense(self.n_classes, name="classifier")(pooled)
            return ({"prediction": logits.astype(jnp.float32)},
                    {"features": pooled})

    def build_gauges(self, batch_shape, n_clients: int) -> dict:
        """Which path the forward's flash calls take (one call a block; none
        with the dense core) and what the remat sites keep
        (``ModelDef.build_gauges``: facts of the build, from abstract traces);
        ``batch_shape`` is one client's [B, T]."""
        keeps = REMAT_KEEPS if self.remat else ()
        x = jax.ShapeDtypeStruct(tuple(batch_shape), jnp.int32)
        with count_call_sites() as sites:
            variables = jax.eval_shape(
                lambda x: self.init(jax.random.PRNGKey(0), x, train=False), x)
        return {**{f"flash_calls_{path}": n for path, n in sites.items()},
                **remat_names.saved_gauges(
                    lambda v, x: self.apply(v, x, train=False)[0][
                        "prediction"],
                    (variables, x), keeps, n_clients)}
