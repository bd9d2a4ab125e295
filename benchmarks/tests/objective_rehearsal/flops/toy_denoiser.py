"""Required FLOPs of one local training step of the toy denoiser: per token
and layer the four d x d projections, the two attention contractions and the
two MLP matmuls, and the token head once; forward + backward = 3 x."""

from __future__ import annotations


def train_step_flops(cfg: dict, job: dict) -> float:
    d, f, seq = cfg["hidden_size"], cfg["intermediate_size"], int(job["data"]["seq"])
    per_tok = ((8.0 * d * d + 4.0 * seq * d + 4.0 * d * f)
               * cfg["num_hidden_layers"] + 2.0 * d * cfg["vocab_size"])
    return 3.0 * per_tok * seq * int(job["batch"])
