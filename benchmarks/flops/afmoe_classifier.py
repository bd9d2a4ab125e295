"""Required FLOPs of one local training step of the AFMoE (Arcee Trinity)
classifier with a frozen base under LoRA adapters, for the layers the
configuration builds (the first ``num_hidden_layers`` of ``layer_types``)
and the share of each expert layer that it holds (``num_experts`` of the
router's ``router_width``).

Forward, per token (a contraction of result M x N over K costs 2*M*N*K):
  attention  q, gate and o 2*d*H*hd each, k and v 2*d*KV*hd each, and the
             two contractions over the scores a query head REQUIRES: on a
             full_attention layer the causal triangle T*(T+1)/2, on a
             sliding_attention layer the band W*T - W*(W-1)/2 (query i sees
             min(i + 1, W) keys; the triangle where T <= W): 2 * 2*hd a
             score and head
  dense      3 * 2*d*intermediate_size, in the num_dense_layers leading layers
  experts    the router 2*d*router_width; the shared expert 3 * 2*d*
             (num_shared_experts * moe_intermediate_size); the routed
             experts at their EXPECTED assignments: a token chooses
             num_experts_per_tok of router_width experts, of which this
             share holds num_experts, so on average top_k * held /
             router_width of its choices are computed here (1.0 at 8 * 16 /
             128), each 3 * 2*d*moe_intermediate_size. The seeded router is
             not trained to balance; the count is the expectation under
             uniform routing over all positions, not what a run's tokens
             chose (a pad position picks none).
A training step on a frozen base needs the forward and the gradients with
respect to the activations: 2 x the forward, not 3 x (no dL/dW of the base;
the rank-8 adapters' own matmuls and gradients add under 1 % and are left
out), counted so for the attention contractions too, as the other adapter
families count them (dQ, dK and dV together are twice the forward's two, so
this leaves a third of the attention backward out: the count errs low). Also
left out and said so: the embedding gather, norms, the rotary rotation,
softmax, the gate's sigmoid, the routing's top-k, sort, gathers and
scatters, the head. Recomputation under remat is never counted, and neither
are the scores of a tile that the window or the diagonal masks.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def scores_per_head(seq: int, window: int | None) -> float:
    """Scores one query head requires over ``seq`` positions."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * seq - window * (window - 1) / 2.0


def attention_flops_per_token(cfg: dict, seq: int, kind: str) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    window = cfg["sliding_window"] if kind == SLIDING else None
    return (3 * (2.0 * d * q) + 2 * (2.0 * d * kv)
            + 2 * 2.0 * scores_per_head(seq, window) * q / seq)


def dense_mlp_flops_per_token(cfg: dict) -> float:
    return 3 * 2.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def expected_local_assignments(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def routed_flops_per_token(cfg: dict) -> float:
    return (expected_local_assignments(cfg)
            * 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"])


def expert_layer_flops_per_token(cfg: dict) -> float:
    d = cfg["hidden_size"]
    shared = (3 * 2.0 * d * cfg["num_shared_experts"]
              * cfg["moe_intermediate_size"])
    return 2.0 * d * cfg["router_width"] + shared + routed_flops_per_token(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    layers = kinds(cfg)
    dense = min(cfg["num_dense_layers"], len(layers))
    return (sum(attention_flops_per_token(cfg, seq, k) for k in layers)
            + dense * dense_mlp_flops_per_token(cfg)
            + (len(layers) - dense) * expert_layer_flops_per_token(cfg))


def train_step_flops(cfg: dict, job: dict) -> float:
    seq = int(job["data"]["seq"])
    return 2.0 * forward_flops_per_token(cfg, seq) * seq * int(job["batch"])
