"""Required FLOPs of one local training step of the Jamba classifier with a
frozen base under LoRA adapters.

Forward, per token (a contraction of result M x N over K costs 2*M*N*K):
  Mamba mixer: in_proj 2*d*2*d_in, x_proj 2*d_in*(R + 2N), dt_proj 2*R*d_in,
               out_proj 2*d_in*d
  attention:   q and o 2*d*d each, k and v 2*d*kv*hd each, and the causal
               half of the two contractions: 2*T*d (not 4*T*d)
  MLP:         3 * 2*d*d_ff, in every layer
A training step on a frozen base needs the forward and the gradients with
respect to the activations: 2 x the forward, not 3 x (no dL/dW of the base;
the rank-8 adapters' own matmuls and gradients add under 1 % and are left
out). Also left out and said so: the embedding gather, norms, the conv, the
selective scan (vector work: flops/selective_scan.py), softmax, the head.
Recomputation under remat is never counted.
"""

from __future__ import annotations


def mixer_flops_per_token(cfg: dict) -> float:
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return 2.0 * d * 2 * d_in + 2.0 * d_in * (r + 2 * n) + 2.0 * r * d_in + 2.0 * d_in * d


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    return 2 * (2.0 * d * d) + 2 * (2.0 * d * kv) + 2.0 * seq * d


def mlp_flops_per_token(cfg: dict) -> float:
    return 3 * 2.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    n = cfg["num_hidden_layers"]
    n_attn = len([i for i in range(n) if i % cfg["attn_layer_period"]
                  == cfg["attn_layer_offset"]])
    return ((n - n_attn) * mixer_flops_per_token(cfg)
            + n_attn * attention_flops_per_token(cfg, seq)
            + n * mlp_flops_per_token(cfg))


def train_step_flops(cfg: dict, job: dict) -> float:
    seq = int(job["data"]["seq"])
    return 2.0 * forward_flops_per_token(cfg, seq) * seq * int(job["batch"])


def mixer_share(cfg: dict, job: dict) -> float:
    """Share of the required FLOPs that the Mamba mixers' matmuls are."""
    seq = int(job["data"]["seq"])
    n = cfg["num_hidden_layers"]
    n_attn = len([i for i in range(n) if i % cfg["attn_layer_period"]
                  == cfg["attn_layer_offset"]])
    return ((n - n_attn) * mixer_flops_per_token(cfg)
            / forward_flops_per_token(cfg, seq))
