"""Operations and bytes that the held routed experts' grouped products need
in one round (models/deepseek.py ``routed_experts``: the three matrices of
each held expert over the rows that chose it).

Operations: the EXPECTED assignments (flops/deepseek_v2_classifier.py: a
token's ``num_experts_per_tok`` choices fall on the ``n_routed_experts`` held
of ``router_width`` with probability held / width each), each 3 * 2*d*f
forward; a training step needs the forward and the gradient with respect to
the rows (the experts are frozen: no dL/dW): 2 x the forward. Bytes: every
held expert's three matrices read once for the forward and once for the
backward of a step, at the compute type (a pass over ~154 rows an expert is
bound by reading the expert, not by its rows), and the rows read and written
at the compute type. Recomputation (the forward again under remat, and again
inside the backward's tiles) is not counted; the evaluation forwards are left
out, so the least time is, if anything, too small and the share too low.
"""

from __future__ import annotations


def expected_rows_per_expert(cfg: dict, tokens: int) -> float:
    return tokens * cfg["num_experts_per_tok"] / cfg["router_width"]


def step_flops(cfg: dict, tokens: int) -> float:
    """Forward and row gradients of one layer over ``tokens`` tokens."""
    rows = expected_rows_per_expert(cfg, tokens) * cfg["n_routed_experts"]
    return 2.0 * rows * 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def step_bytes(cfg: dict, tokens: int, item: int = 2) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = expected_rows_per_expert(cfg, tokens) * cfg["n_routed_experts"]
    weights = cfg["n_routed_experts"] * 3 * d * f * item
    # forward: rows in, rows out; backward: rows and their cotangents in,
    # row gradients out
    return 2.0 * weights + 5.0 * rows * d * item


def expert_layers(cfg: dict) -> int:
    n = cfg["num_hidden_layers"]
    return n - min(cfg["first_k_dense_replace"], n)


def least_seconds_per_round(cfg: dict, job: dict, peak_flops: float,
                            peak_bytes: float):
    """(seconds, bound). The clients' tokens of one local step are ONE call's
    rows (the client axis is folded), so the experts are read once a step,
    not once a client."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    tokens = int(job["clients"]) * int(job["batch"]) * int(job["data"]["seq"])
    calls = expert_layers(cfg) * int(job["local_steps"])
    tc = calls * step_flops(cfg, tokens) / peak_flops
    tm = calls * step_bytes(cfg, tokens, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
