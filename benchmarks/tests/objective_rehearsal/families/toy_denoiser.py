"""Adapter and model in one: the toy token denoiser as the program takes it,
a flax module written here (the program has no denoiser of its own), whose
parameter tree has the names of ``reference/toy_denoiser.param_spec``."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


class ToyDenoiser(nn.Module):
    vocab: int
    d: int
    heads: int
    d_ff: int
    layers: int
    positions: int
    dtype: Any

    @nn.compact
    def __call__(self, x, train: bool = True):
        tokens = x[:, 0]  # the noised row; row 1 is the clean ids, the loss's
        b, t = tokens.shape
        hd = self.d // self.heads
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        norm = lambda name: nn.LayerNorm(dtype=self.dtype, name=name)  # noqa: E731
        pad_mask = tokens > 0
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.positions, self.d))
        h = (nn.Embed(self.vocab, self.d, dtype=self.dtype,
                      name="tok_embed")(tokens) + pos[None, :t].astype(self.dtype))
        for i in range(self.layers):
            a = norm(f"l{i}_ln_attn")(h)
            q, k, v = (dense(self.d, f"l{i}_{p}")(a).reshape(b, t, self.heads, hd)
                       for p in ("q", "k", "v"))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                jnp.float32) / jnp.sqrt(jnp.float32(hd))
            scores = jnp.where(pad_mask[:, None, None, :], scores,
                               jnp.finfo(jnp.float32).min)
            attn = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, t, self.d)
            h = h + dense(self.d, f"l{i}_o")(out)
            m = nn.gelu(dense(self.d_ff, f"l{i}_ff_in")(norm(f"l{i}_ln_mlp")(h)))
            h = h + dense(self.d, f"l{i}_ff_out")(m)
        logits = nn.Dense(self.vocab, dtype=self.dtype, name="head")(
            norm("ln_final")(h))
        return {"prediction": logits.astype(jnp.float32)}, {}


def build_module(cfg: dict, job: dict):
    return ToyDenoiser(
        vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"],
        positions=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]))
