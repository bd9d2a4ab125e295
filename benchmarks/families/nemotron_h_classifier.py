"""Adapter: the program's ``NemotronHClassifier`` built from a configuration
file and a traffic file. The only place that names the program's model class
for this family. The module brings its own split of the parameters (adapters
and head per client, the base shared), which ``engine.from_flax`` reads. The
blocks built are the first ``num_hidden_layers`` of the published
``hybrid_override_pattern``; the configuration's ``n_routed_experts`` is the
experts HELD here, the router's width is ``router_width``."""

from __future__ import annotations

import functools


def build_module(cfg: dict, job: dict):
    import jax.numpy as jnp

    from fl4health_tpu.models.nemotron_h import NemotronHClassifier

    attention_fn = None
    att = job.get("attention") or {"kind": "dense"}
    if att["kind"] == "flash":
        from fl4health_tpu.kernels.flash_attention import flash_attention

        attention_fn = functools.partial(
            flash_attention, causal=True, block_q=int(att["block_q"]),
            block_k=int(att["block_k"]))
    elif att["kind"] != "dense":
        raise ValueError(f"unknown attention kind {att['kind']!r}")
    return NemotronHClassifier(
        vocab_size=cfg["vocab_size"], n_classes=cfg["num_labels"],
        pattern=cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        d_conv=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        first_expert_held=cfg["first_expert_held"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        d_latent=cfg["moe_latent_size"], d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        rms_eps=cfg["layer_norm_epsilon"], lora_rank=cfg["lora_rank"],
        lora_alpha=float(cfg["lora_alpha"]),
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=bool(job.get("remat")),
        attention_fn=attention_fn,
    )
