"""Operations and bytes that the held routed experts' grouped products need
in one executed pass where an expert is a SwiGLU of THREE matrices on the
model's width and the configuration names its share as ``models/afmoe.py``
does (``num_experts`` held of the router's ``router_width``, the leading
``num_dense_layers`` layers without experts; through models/deepseek.py
``routed_experts`` with ``swiglu_expert``). ``flops/moe_experts.py`` counts
the same products from DeepSeek's keys and by the traffic file's passes,
``flops/routed_latent_experts.py`` two matrices on a latent.

Operations: the EXPECTED assignments (a token's ``num_experts_per_tok``
choices fall on the held experts with probability held / width each: 1.0 a
token at 8 * 16 / 128), each 3 * 2*d*f forward; the backward (the gradient
with respect to the rows; the experts are frozen: no dL/dW) is as much
again, and the forward it recomputes inside its tiles is not counted. Bytes:
every held expert's three matrices read once a pass at the compute type,
the rows read and written. A round's rows are the clients' REAL positions (a
pad position picks no expert here).
"""

from __future__ import annotations


def real_positions(job: dict) -> float:
    """Expected tokens of a row: the generator draws a length uniformly in
    [seq * min_len_frac, seq] and pads the tail."""
    seq = int(job["data"]["seq"])
    lo = max(1, int(seq * float(job["data"].get("min_len_frac", 1.0))))
    return (lo + seq) / 2.0


def expected_rows(cfg: dict, tokens: float) -> float:
    """Rows of all the held experts together."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def pass_flops(cfg: dict, tokens: float) -> float:
    """One pass (forward, or the row gradients) of one layer."""
    return (expected_rows(cfg, tokens) * 3 * 2.0 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def pass_bytes(kind: str, cfg: dict, tokens: float, item: int = 2) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * 3 * d * f * item
    # forward: rows in, rows out; backward: rows and their cotangents in,
    # row gradients out
    return weights + {"fwd": 2.0, "bwd": 3.0}[kind] * expected_rows(
        cfg, tokens) * d * item


def expert_layers(cfg: dict) -> int:
    n = cfg["num_hidden_layers"]
    return n - min(cfg["num_dense_layers"], n)


def least_seconds(kind: str, cfg: dict, tokens: float, peak_flops: float,
                  peak_bytes: float, item: int = 2):
    tc = pass_flops(cfg, tokens) / peak_flops
    tm = pass_bytes(kind, cfg, tokens, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def least_seconds_per_round(cfg: dict, job: dict, peak_flops: float,
                            peak_bytes: float, passes) -> float:
    """The training passes of a round that ``passes`` names (the passes a
    trace SHOWS under the scope: ``forward`` and ``recompute`` each cost a
    forward, ``backward`` the row gradients), each local steps x expert
    layers times: the clients' tokens of one local step are ONE call's rows
    (the client axis is folded), so the experts are read once a pass."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    tokens = int(job["clients"]) * int(job["batch"]) * real_positions(job)
    kinds = {"forward": "fwd", "recompute": "fwd", "backward": "bwd"}
    one = sum(least_seconds(kinds[p], cfg, tokens, peak_flops, peak_bytes,
                            item)[0] for p in passes if p in kinds)
    return one * expert_layers(cfg) * int(job["local_steps"])
