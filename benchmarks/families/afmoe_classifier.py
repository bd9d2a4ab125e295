"""Adapter: the program's ``AfmoeClassifier`` built from a configuration file
and a traffic file. The only place that names the program's model class for
this family. The module brings its own split of the parameters (adapters and
head per client, the base shared), which ``engine.from_flax`` reads. The
layers built are the first ``num_hidden_layers`` of the published
``layer_types``; the configuration's ``num_experts`` is the experts HELD
here, the router's width is ``router_width``. The attention function is the
causal one of the other adapter families; the module passes it ``window`` on
its sliding layers."""

from __future__ import annotations

import functools


def build_module(cfg: dict, job: dict):
    import jax.numpy as jnp

    from fl4health_tpu.models.afmoe import AfmoeClassifier

    attention_fn = None
    att = job.get("attention") or {"kind": "dense"}
    if att["kind"] == "flash":
        from fl4health_tpu.kernels.flash_attention import flash_attention

        attention_fn = functools.partial(
            flash_attention, causal=True, block_q=int(att["block_q"]),
            block_k=int(att["block_k"]))
    elif att["kind"] != "dense":
        raise ValueError(f"unknown attention kind {att['kind']!r}")
    if cfg.get("rope_scaling"):
        raise ValueError("afmoe: only plain rotary positions are built "
                         f"(rope_scaling {cfg['rope_scaling']!r})")
    if not cfg["mup_enabled"]:
        raise ValueError("afmoe: the embedding is built times "
                         "sqrt(hidden_size) alone (mup_enabled false)")
    return AfmoeClassifier(
        vocab_size=cfg["vocab_size"], n_classes=cfg["num_labels"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        num_dense_layers=cfg["num_dense_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        # a configuration cut to fewer query heads than it has key/value
        # heads (the CPU tests' toy twins) keeps a key/value head a query head
        n_kv_heads=min(cfg["num_key_value_heads"],
                       cfg["num_attention_heads"]),
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        first_expert_held=cfg["first_expert_held"],
        n_shared_experts=cfg["num_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        route_scale=float(cfg["route_scale"]),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        lora_rank=cfg["lora_rank"],
        lora_alpha=float(cfg["lora_alpha"]),
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=bool(job.get("remat")),
        attention_fn=attention_fn,
    )
