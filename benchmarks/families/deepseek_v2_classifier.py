"""Adapter: the program's ``DeepseekV2Classifier`` built from a configuration
file and a traffic file. The only place that names the program's model class
for this family. The module brings its own split of the parameters (adapters
and head per client, the base shared), which ``engine.from_flax`` reads. The
configuration's ``n_routed_experts`` is the experts HELD here; the router's
width is ``router_width``."""

from __future__ import annotations

import functools


def build_module(cfg: dict, job: dict):
    import jax.numpy as jnp

    from fl4health_tpu.models.deepseek import (DeepseekV2Classifier,
                                               RopeScaling)

    attention_fn = None
    att = job.get("attention") or {"kind": "dense"}
    if att["kind"] == "flash":
        from fl4health_tpu.kernels.flash_attention import flash_attention

        attention_fn = functools.partial(
            flash_attention, causal=True, block_q=int(att["block_q"]),
            block_k=int(att["block_k"]))
    elif att["kind"] != "dense":
        raise ValueError(f"unknown attention kind {att['kind']!r}")
    scaling = cfg.get("rope_scaling") or {}
    if scaling and scaling.get("type") != "yarn":
        raise ValueError(f"unknown rope_scaling type {scaling.get('type')!r}")
    rope = RopeScaling(
        theta=float(cfg["rope_theta"]), factor=float(scaling.get("factor", 1)),
        beta_fast=float(scaling.get("beta_fast", 32)),
        beta_slow=float(scaling.get("beta_slow", 1)),
        original_max_position=int(scaling.get(
            "original_max_position_embeddings",
            cfg["max_position_embeddings"])),
        mscale=float(scaling.get("mscale", 1)),
        mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    return DeepseekV2Classifier(
        vocab_size=cfg["vocab_size"], n_classes=cfg["num_labels"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_expert=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        first_expert_held=cfg["first_expert_held"],
        n_shared_experts=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), rope=rope,
        rms_eps=cfg["rms_norm_eps"], lora_rank=cfg["lora_rank"],
        lora_alpha=float(cfg["lora_alpha"]),
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=bool(job.get("remat")),
        attention_fn=attention_fn,
    )
