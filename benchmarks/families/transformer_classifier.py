"""Adapter: the program's ``TransformerClassifier`` built from a
configuration file and a traffic file. The only place that names the
program's model class for this family."""

from __future__ import annotations

import functools


def build_module(cfg: dict, job: dict):
    import jax.numpy as jnp

    from fl4health_tpu.models.transformer import TransformerClassifier

    attention_fn = None
    att = job.get("attention") or {"kind": "dense"}
    if att["kind"] == "flash":
        from fl4health_tpu.kernels.flash_attention import flash_attention

        attention_fn = functools.partial(
            flash_attention, block_q=int(att["block_q"]),
            block_k=int(att["block_k"]))
    elif att["kind"] != "dense":
        raise ValueError(f"unknown attention kind {att['kind']!r}")
    return TransformerClassifier(
        vocab_size=cfg["vocab_size"], n_classes=cfg["num_labels"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=int(job.get("max_positions") or cfg["max_position_embeddings"]),
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=bool(job.get("remat")),
        attention_fn=attention_fn,
    )
