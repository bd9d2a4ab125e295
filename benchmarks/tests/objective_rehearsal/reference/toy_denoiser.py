"""Plain reference of the toy token denoiser: a two-layer pre-LN encoder over
the noised row of ``x`` with a token head over the vocabulary. float32,
jax.numpy only, imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def param_spec(cfg: dict, job: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    spec = {
        "tok_embed/embedding": ((v, d), "fan_in"),
        "pos_embed": ((cfg["max_position_embeddings"], d), "embed"),
        "ln_final/scale": ((d,), "ones"), "ln_final/bias": ((d,), "zeros"),
        "head/kernel": ((d, v), "fan_in"), "head/bias": ((v,), "zeros"),
    }
    for i in range(cfg["num_hidden_layers"]):
        for ln in ("ln_attn", "ln_mlp"):
            spec[f"l{i}_{ln}/scale"] = ((d,), "ones")
            spec[f"l{i}_{ln}/bias"] = ((d,), "zeros")
        for proj in ("q", "k", "v", "o"):
            spec[f"l{i}_{proj}/kernel"] = ((d, d), "fan_in")
        spec[f"l{i}_ff_in/kernel"] = ((d, f), "fan_in")
        spec[f"l{i}_ff_out/kernel"] = ((f, d), "fan_in")
    return spec


def input_spec(cfg: dict, job: dict) -> dict:
    # words are ids 1 .. vocab_size - 2: the last id is the mask token's
    return {"kind": "tokens", "vocab": cfg["vocab_size"] - 1,
            "seq": job["data"]["seq"],
            "min_len_frac": job["data"].get("min_len_frac", 1.0)}


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """x: int tokens [B, 2, T], row 0 noised and row 1 clean. Returns float32
    logits [B, T, vocab] from the NOISED row; a padded key is masked out."""
    tokens = x[:, 0]
    b, t = tokens.shape
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    pad_mask = tokens > 0
    h = params["tok_embed/embedding"][tokens] + params["pos_embed"][None, :t]
    for i in range(cfg["num_hidden_layers"]):
        a = _layer_norm(h, params[f"l{i}_ln_attn/scale"],
                        params[f"l{i}_ln_attn/bias"])
        q, k, v = (nm.dot(a, params[f"l{i}_{p}/kernel"]).reshape(b, t, heads, hd)
                   for p in ("q", "k", "v"))
        scores = nm.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(pad_mask[:, None, None, :], scores,
                           jnp.finfo(jnp.float32).min)
        out = nm.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        h = h + nm.dot(out.reshape(b, t, heads * hd), params[f"l{i}_o/kernel"])
        m = _layer_norm(h, params[f"l{i}_ln_mlp/scale"],
                        params[f"l{i}_ln_mlp/bias"])
        m = jax.nn.gelu(nm.dot(m, params[f"l{i}_ff_in/kernel"]), approximate=True)
        h = h + nm.dot(m, params[f"l{i}_ff_out/kernel"])
    h = _layer_norm(h, params["ln_final/scale"], params["ln_final/bias"])
    return nm.dot(h, params["head/kernel"]) + params["head/bias"]
