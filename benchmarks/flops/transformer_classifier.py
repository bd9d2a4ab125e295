"""Required FLOPs of one local training step of the encoder classifier.

Per token and layer, forward: the four d x d projections 8*d^2, the two
attention contractions 4*T*d (QK^T and PV, each 2*T*d), the two MLP
matmuls 4*d*d_ff. Left out and said so: the embedding lookup and its
gradient scatter, LayerNorms, softmax, GELU, the pooled 4-class head.
Copied from fl4health_tpu/observability/flops.py transformer_round_flops.
"""

from __future__ import annotations


def forward_flops_per_token(d: int, d_ff: int, layers: int, seq: int) -> float:
    return (8.0 * d * d + 4.0 * seq * d + 4.0 * d * d_ff) * layers


def train_step_flops(cfg: dict, job: dict) -> float:
    seq = int(job["data"]["seq"])
    per_tok = forward_flops_per_token(
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], seq)
    return 3.0 * per_tok * seq * int(job["batch"])


def attention_share(cfg: dict, job: dict) -> float:
    """Share of the required FLOPs that the two attention contractions are."""
    seq, d = int(job["data"]["seq"]), cfg["hidden_size"]
    return 4.0 * seq * d / (8.0 * d * d + 4.0 * seq * d
                            + 4.0 * d * cfg["intermediate_size"])
