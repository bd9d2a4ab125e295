"""Plain reference of the encoder classifier: BERT-base-width pre-LN blocks,
masked mean pool, linear head. float32, jax.numpy only, no kernels.

Departures from the published BERT (also listed under ``assumed`` in the
configuration file): pre-LN residual blocks, tanh-approximate GELU,
LayerNorm eps 1e-6, no segment embeddings, classifier on the masked mean of
the final LayerNorm'd states. Token id 0 is padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def sizes(cfg: dict, job: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"], "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "classes": cfg["num_labels"],
        "positions": int(job.get("max_positions")
                         or cfg["max_position_embeddings"]),
    }


def param_spec(cfg: dict, job: dict) -> dict:
    """path -> (shape, init). Paths are '/'-joined names."""
    s = sizes(cfg, job)
    d, f = s["d"], s["d_ff"]
    spec = {
        "tok_embed/embedding": ((s["vocab"], d), "embed"),
        "pos_embed": ((s["positions"], d), "embed"),
        "ln_final/scale": ((d,), "ones"), "ln_final/bias": ((d,), "zeros"),
        "classifier/kernel": ((d, s["classes"]), "fan_in"),
        "classifier/bias": ((s["classes"],), "zeros"),
    }
    for i in range(s["layers"]):
        p = f"layer_{i}"
        for ln in ("ln_attn", "ln_mlp"):
            spec[f"{p}/{ln}/scale"] = ((d,), "ones")
            spec[f"{p}/{ln}/bias"] = ((d,), "zeros")
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            spec[f"{p}/attn/{proj}/kernel"] = ((d, d), "fan_in")
            spec[f"{p}/attn/{proj}/bias"] = ((d,), "zeros")
        spec[f"{p}/ff_in/kernel"] = ((d, f), "fan_in")
        spec[f"{p}/ff_in/bias"] = ((f,), "zeros")
        spec[f"{p}/ff_out/kernel"] = ((f, d), "fan_in")
        spec[f"{p}/ff_out/bias"] = ((d,), "zeros")
    return spec


def input_spec(cfg: dict, job: dict) -> dict:
    return {"kind": "tokens", "vocab": cfg["vocab_size"],
            "seq": job["data"]["seq"], "classes": cfg["num_labels"],
            "min_len_frac": job["data"].get("min_len_frac", 1.0)}


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _block(p, h, pad_mask, n_heads, nm):
    """One pre-LN block; ``p`` holds that layer's arrays by their names
    inside the layer ('attn/q_proj/kernel', ...)."""
    def dense(x, name):
        return nm.dot(x, p[f"{name}/kernel"]) + p[f"{name}/bias"]

    b, t, d = h.shape
    hd = d // n_heads
    x = _layer_norm(h, p["ln_attn/scale"], p["ln_attn/bias"])
    q = dense(x, "attn/q_proj").reshape(b, t, n_heads, hd)
    k = dense(x, "attn/k_proj").reshape(b, t, n_heads, hd)
    v = dense(x, "attn/v_proj").reshape(b, t, n_heads, hd)
    scores = nm.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(pad_mask[:, None, None, :] > 0, scores,
                       jnp.finfo(jnp.float32).min)
    attn = jax.nn.softmax(scores, axis=-1)
    out = nm.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, t, d)
    h = h + dense(out, "attn/o_proj")
    x = _layer_norm(h, p["ln_mlp/scale"], p["ln_mlp/bias"])
    x = jax.nn.gelu(dense(x, "ff_in"), approximate=True)
    return h + dense(x, "ff_out")


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """params: flat path -> float32 array. x: int tokens [B, T]. Returns
    float32 logits [B, classes]. The layers' arrays are stacked and the
    blocks run as one ``lax.scan`` (one block compiled, not one per layer:
    unrolled, the float32 ``highest`` program was 120 MB in the compile
    cache), each block rematerialised on the backward pass so a long
    sequence's score matrices fit. Neither changes a value."""
    s = sizes(cfg, job)
    pad_mask = (x > 0).astype(jnp.float32)
    h = params["tok_embed/embedding"][x] + params["pos_embed"][None, : x.shape[1]]
    names = [k[len("layer_0/"):] for k in params if k.startswith("layer_0/")]
    stacked = {n: jnp.stack([params[f"layer_{i}/{n}"]
                             for i in range(s["layers"])]) for n in names}
    block = jax.checkpoint(
        lambda h_, lp: (_block(lp, h_, pad_mask, s["heads"], nm), None))
    h, _ = jax.lax.scan(block, h, stacked)
    h = _layer_norm(h, params["ln_final/scale"], params["ln_final/bias"])
    denom = jnp.maximum(pad_mask.sum(axis=1, keepdims=True), 1.0)
    pooled = (h * pad_mask[..., None]).sum(axis=1) / denom
    return nm.dot(pooled, params["classifier/kernel"]) + params["classifier/bias"]
