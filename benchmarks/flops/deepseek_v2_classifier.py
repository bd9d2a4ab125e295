"""Required FLOPs of one local training step of the DeepSeek-V2 classifier
with a frozen base under LoRA adapters, for the share of each layer that the
configuration holds (``n_routed_experts`` of the router's ``router_width``).

Forward, per token (a contraction of result M x N over K costs 2*M*N*K):
  MLA:      q_a 2*d*q_rank, q_b 2*q_rank*H*(nope + rope), kv_a
            2*d*(kv_rank + rope), kv_b 2*kv_rank*H*(nope + v), o 2*H*v*d, and
            the causal half of the two contractions: T*H*(nope + rope) for
            S = Q K^T and T*H*v for P V (not twice that)
  dense:    3 * 2*d*intermediate, in the first_k_dense_replace leading layers
  experts:  the router 2*d*router_width; the shared experts 3 * 2*d*(n_shared
            * moe_intermediate); the routed experts at their EXPECTED
            assignments: a token chooses num_experts_per_tok of router_width
            experts, of which this share holds n_routed_experts, so on
            average top_k * held / router_width of its choices are computed
            here (0.3 at 6 * 8 / 160), each 3 * 2*d*moe_intermediate. The
            seeded router is not trained to balance; the count is the
            expectation under uniform routing, not what a run's tokens chose.
A training step on a frozen base needs the forward and the gradients with
respect to the activations: 2 x the forward, not 3 x (no dL/dW of the base;
the rank-8 adapters' own matmuls and gradients add under 1 % and are left
out). Also left out and said so: the embedding gather, norms, RoPE, softmax,
the routing's sort, gathers and scatters, the head. Recomputation under remat
is never counted.
"""

from __future__ import annotations


def mla_flops_per_token(cfg: dict, seq: int) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    proj = (2.0 * d * cfg["q_lora_rank"]
            + 2.0 * cfg["q_lora_rank"] * h * (nope + rope)
            + 2.0 * d * (cfg["kv_lora_rank"] + rope)
            + 2.0 * cfg["kv_lora_rank"] * h * (nope + v)
            + 2.0 * h * v * d)
    return proj + 1.0 * seq * h * (nope + rope) + 1.0 * seq * h * v


def dense_mlp_flops_per_token(cfg: dict) -> float:
    return 3 * 2.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def expected_local_assignments(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def routed_flops_per_token(cfg: dict) -> float:
    return (expected_local_assignments(cfg)
            * 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"])


def expert_layer_flops_per_token(cfg: dict) -> float:
    d = cfg["hidden_size"]
    shared = 3 * 2.0 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return 2.0 * d * cfg["router_width"] + shared + routed_flops_per_token(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return (n * mla_flops_per_token(cfg, seq)
            + dense * dense_mlp_flops_per_token(cfg)
            + (n - dense) * expert_layer_flops_per_token(cfg))


def train_step_flops(cfg: dict, job: dict) -> float:
    seq = int(job["data"]["seq"])
    return 2.0 * forward_flops_per_token(cfg, seq) * seq * int(job["batch"])


def routed_share(cfg: dict, job: dict) -> float:
    """Share of the required FLOPs that the routed experts' products are."""
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return ((n - dense) * routed_flops_per_token(cfg)
            / forward_flops_per_token(cfg, int(job["data"]["seq"])))
