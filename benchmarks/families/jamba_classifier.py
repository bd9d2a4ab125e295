"""Adapter: the program's ``JambaClassifier`` built from a configuration
file and a traffic file. The only place that names the program's model class
for this family. The module brings its own split of the parameters (adapters
and head per client, the base shared), which ``engine.from_flax`` reads."""

from __future__ import annotations

import functools


def build_module(cfg: dict, job: dict):
    import jax.numpy as jnp

    from fl4health_tpu.models.jamba import JambaClassifier

    attention_fn = None
    att = job.get("attention") or {"kind": "dense"}
    if att["kind"] == "flash":
        from fl4health_tpu.kernels.flash_attention import flash_attention

        attention_fn = functools.partial(
            flash_attention, causal=True, block_q=int(att["block_q"]),
            block_k=int(att["block_k"]))
    elif att["kind"] != "dense":
        raise ValueError(f"unknown attention kind {att['kind']!r}")
    return JambaClassifier(
        vocab_size=cfg["vocab_size"], n_classes=cfg["num_labels"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mamba_expand=cfg["mamba_expand"], d_state=cfg["mamba_d_state"],
        dt_rank=cfg["mamba_dt_rank"], d_conv=cfg["mamba_d_conv"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        rms_eps=cfg["rms_norm_eps"], lora_rank=cfg["lora_rank"],
        lora_alpha=float(cfg["lora_alpha"]),
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=bool(job.get("remat")),
        attention_fn=attention_fn,
    )
