"""Operations and bytes that one executed pass of the selective scan
(kernels/selective_scan.py) needs, over ``tokens`` positions of ``d_inner``
channels with a ``d_state``-wide state per channel.

Bytes are the least a pass must move: the state never leaves the chip.
  forward:   reads x, Delta, z, writes y (``item`` bytes each, per token and
             channel); reads B and C (float32, per token and state)
  backward:  reads x, Delta, z, dy, writes dx, dDelta, dz; reads B and C,
             writes dB and dC
Vector operations per token, channel and state, forward: one exponential and
about six multiplies and adds (decay * s, Delta*x*B, the sum, s * C and its
reduction); the backward about three times that. ``peaks.json`` has no
vector-unit peak to hold them against, so only the bytes enter a roofline.
"""

from __future__ import annotations

FORWARD_OPS = 7.0
BACKWARD_OPS = 21.0


def pass_bytes(kind: str, tokens: int, d_inner: int, d_state: int,
               item: int = 2) -> float:
    big, small = tokens * d_inner * item, tokens * d_state * 4
    if kind == "fwd":
        return 4.0 * big + 2.0 * small
    if kind == "bwd":
        return 7.0 * big + 4.0 * small
    raise KeyError(kind)


def pass_ops(kind: str, tokens: int, d_inner: int, d_state: int) -> float:
    per = {"fwd": FORWARD_OPS, "bwd": BACKWARD_OPS}[kind]
    return per * tokens * d_inner * d_state


def passes_per_step(remat: bool) -> dict:
    """Executed passes per Mamba layer per local training step: the forward
    runs again on the backward pass under remat."""
    return {"fwd": 2 if remat else 1, "bwd": 1}


def mamba_layers(cfg: dict) -> int:
    n = cfg["num_hidden_layers"]
    return n - len([i for i in range(n) if i % cfg["attn_layer_period"]
                    == cfg["attn_layer_offset"]])


def least_seconds_per_round(cfg: dict, job: dict, hbm_bytes_per_s: float) -> float:
    """Every training pass of a round (clients x local steps x Mamba layers);
    the evaluation forwards are left out, so the least time is, if anything,
    too small and the share too low."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    tokens = int(job["batch"]) * int(job["data"]["seq"])
    d_inner = cfg["mamba_expand"] * cfg["hidden_size"]
    per_step = sum(n * pass_bytes(kind, tokens, d_inner, cfg["mamba_d_state"],
                                  item)
                   for kind, n in passes_per_step(bool(job.get("remat"))).items())
    steps = int(job["clients"]) * int(job["local_steps"])
    return per_step * mamba_layers(cfg) * steps / hbm_bytes_per_s
