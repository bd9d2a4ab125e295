"""Operations and bytes that the held routed experts' grouped products need
in one executed pass where an expert is TWO matrices on a latent
(models/nemotron_h.py through models/deepseek.py ``routed_experts``:
``relu(l W_up)^2 W_down``, latent -> moe_intermediate -> latent).
``flops/moe_experts.py`` counts three matrices from DeepSeek's keys.

Operations: the EXPECTED assignments (a token's ``num_experts_per_tok``
choices fall on the ``n_routed_experts`` held of ``router_width`` with
probability held / width each: 0.6875 a token at 22 * 16 / 512), each 2 *
2*latent*f forward; the backward (the gradient with respect to the rows; the
experts are frozen: no dL/dW) is as much again, and the forward it
recomputes inside its tiles is not counted. Bytes: every held expert's two
matrices read once a pass at the compute type (a pass over ~350 rows an
expert is bound by reading the expert), the rows read and written. A round's
rows are the clients' REAL positions (pad positions pick no expert here).
"""

from __future__ import annotations


def real_positions(job: dict) -> float:
    """Expected tokens of a row: the generator draws a length uniformly in
    [seq * min_len_frac, seq] and pads the tail, and a pad position picks no
    expert here (``models/nemotron_h.py latent_moe``), so it is no row."""
    seq = int(job["data"]["seq"])
    lo = max(1, int(seq * float(job["data"].get("min_len_frac", 1.0))))
    return (lo + seq) / 2.0


def expected_rows_per_expert(cfg: dict, tokens: float) -> float:
    return tokens * cfg["num_experts_per_tok"] / cfg["router_width"]


def pass_flops(cfg: dict, tokens: int) -> float:
    """One pass (forward, or the row gradients) of one layer."""
    rows = expected_rows_per_expert(cfg, tokens) * cfg["n_routed_experts"]
    return rows * 2 * 2.0 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def pass_bytes(kind: str, cfg: dict, tokens: int, item: int = 2) -> float:
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    rows = expected_rows_per_expert(cfg, tokens) * cfg["n_routed_experts"]
    weights = cfg["n_routed_experts"] * 2 * lat * f * item
    # forward: rows in, rows out; backward: rows and their cotangents in,
    # row gradients out
    return weights + {"fwd": 2.0, "bwd": 3.0}[kind] * rows * lat * item


def expert_blocks(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]].count("E")


def least_seconds(kind: str, cfg: dict, tokens: int, peak_flops: float,
                  peak_bytes: float, item: int = 2):
    tc = pass_flops(cfg, tokens) / peak_flops
    tm = pass_bytes(kind, cfg, tokens, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def least_seconds_per_round(cfg: dict, job: dict, peak_flops: float,
                            peak_bytes: float, passes) -> float:
    """The training passes of a round that ``passes`` names (the passes a
    trace SHOWS under the scope: ``forward`` and ``recompute`` each cost a
    forward, ``backward`` the row gradients), each local steps x expert
    blocks times: the clients' tokens of one local step are ONE call's rows
    (the client axis is folded), so the experts are read once a pass."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    tokens = int(job["clients"]) * int(job["batch"]) * real_positions(job)
    kinds = {"forward": "fwd", "recompute": "fwd", "backward": "bwd"}
    one = sum(least_seconds(kinds[p], cfg, tokens, peak_flops, peak_bytes,
                            item)[0] for p in passes if p in kinds)
    return one * expert_blocks(cfg) * int(job["local_steps"])
