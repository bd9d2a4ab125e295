"""Required FLOPs of one local training step of the Nemotron-H classifier
with a frozen base under LoRA adapters, for the blocks the configuration
builds (the first ``num_hidden_layers`` of ``hybrid_override_pattern``) and
the share of each expert layer that it holds (``n_routed_experts`` of the
router's ``router_width``).

Forward, per token (a contraction of result M x N over K costs 2*M*N*K):
  M  in_proj 2*d*(2*d_inner + 2*G*N + H), out_proj 2*d_inner*d, and the
     chunked scan's matmuls (flops/ssd_scan.py: C B^T a group, the decayed
     scores times the chunk's inputs, the chunk's state, the incoming
     state's part; whole Q x Q tiles, as the chunked form computes them)
  *  q and o 2*d*H*hd each, k and v 2*d*KV*hd each, and the causal half of
     the two contractions: 2*T*H*hd (not 4*T*H*hd)
  E  the router 2*d*router_width; the two latent projections 2*d*latent
     each; the shared expert 2 * 2*d*shared; the routed experts at their
     EXPECTED assignments: a token chooses num_experts_per_tok of
     router_width experts, of which this share holds n_routed_experts, so on
     average top_k * held / router_width of its choices are computed here
     (0.6875 at 22 * 16 / 512), each 2 * 2*latent*moe_intermediate. The
     seeded router is not trained to balance; the count is the expectation
     under uniform routing, not what a run's tokens chose.
A training step on a frozen base needs the forward and the gradients with
respect to the activations: 2 x the forward, not 3 x (no dL/dW of the base;
the rank-8 adapters' own matmuls and gradients add under 1 % and are left
out). Also left out and said so: the embedding gather, norms, the conv, the
scan's exponentials and elementwise products, softmax, the routing's top-k,
sort, gathers and scatters, the head. Recomputation under remat is never
counted.
"""

from __future__ import annotations

import os


def _ssd():
    from benchmarks.harness.spec import load_module

    return load_module("flops", "ssd_scan", os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def pattern(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def mamba_flops_per_token(cfg: dict) -> float:
    d = cfg["hidden_size"]
    h = cfg["mamba_num_heads"]
    d_inner = h * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return (2.0 * d * (2 * d_inner + 2 * gn + h) + 2.0 * d_inner * d
            + _ssd().forward_flops_per_token(cfg))


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2 * (2.0 * d * q) + 2 * (2.0 * d * kv) + 2.0 * seq * q


def expected_local_assignments(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def routed_flops_per_token(cfg: dict) -> float:
    return (expected_local_assignments(cfg) * 2 * 2.0
            * cfg["moe_latent_size"] * cfg["moe_intermediate_size"])


def expert_block_flops_per_token(cfg: dict) -> float:
    d = cfg["hidden_size"]
    return (2.0 * d * cfg["router_width"]
            + 2 * 2.0 * d * cfg["moe_latent_size"]
            + 2 * 2.0 * d * cfg["moe_shared_expert_intermediate_size"]
            + routed_flops_per_token(cfg))


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    p = pattern(cfg)
    return (p.count("M") * mamba_flops_per_token(cfg)
            + p.count("*") * attention_flops_per_token(cfg, seq)
            + p.count("E") * expert_block_flops_per_token(cfg))


def train_step_flops(cfg: dict, job: dict) -> float:
    seq = int(job["data"]["seq"])
    return 2.0 * forward_flops_per_token(cfg, seq) * seq * int(job["batch"])


def mamba_share(cfg: dict, job: dict) -> float:
    """Share of the required FLOPs that the Mamba-2 blocks are."""
    return (pattern(cfg).count("M") * mamba_flops_per_token(cfg)
            / forward_flops_per_token(cfg, int(job["data"]["seq"])))


def routed_share(cfg: dict, job: dict) -> float:
    """Share of the required FLOPs that the routed experts' products are."""
    return (pattern(cfg).count("E") * routed_flops_per_token(cfg)
            / forward_flops_per_token(cfg, int(job["data"]["seq"])))
