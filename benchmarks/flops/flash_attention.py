"""Operations and bytes that one executed call of each flash-attention
kernel needs (kernels/flash_attention.py), over ``bh`` (batch x heads) rows
of ``t`` positions of ``d`` lanes, both as padded by the wrapper (d to 64 for
heads of at most 64, t to the block grid).

Dots per call, each 2*t*t*d per row:
  forward:  S = Q K^T, O = P V                                -> 4*t*t*d
  dQ:       S (recomputed), dP = dO V^T, dQ = dS K            -> 6*t*t*d
  dK/dV:    S (recomputed), dV = P^T dO, dP = dO V^T, dK = dS^T Q -> 8*t*t*d
Bytes are the least the call must move: every operand read once, every
result written once (``item`` bytes for q/k/v/o/do/dq/dk/dv, 4 for the
mask, lse and delta rows).
"""

from __future__ import annotations

DOTS = {"fwd": 2, "dq": 3, "dkv": 4}


def padded(t: int, d: int, block_q: int, block_k: int):
    import math

    tm = math.lcm(block_q, block_k)
    dm = 64 if d <= 64 else 128
    return -(-t // tm) * tm, -(-d // dm) * dm


def call_flops(kind: str, bh: int, t: int, d: int) -> float:
    return DOTS[kind] * 2.0 * t * t * d * bh


def call_bytes(kind: str, bh: int, t: int, d: int, item: int = 2) -> float:
    big, row = bh * t * d * item, bh * t * 4
    if kind == "fwd":  # q k v -> o, lse; mask
        return 4 * big + 2 * row
    if kind == "dq":  # q k v do -> dq; mask lse delta
        return 5 * big + 3 * row
    if kind == "dkv":  # q k v do -> dk dv; mask lse delta
        return 6 * big + 3 * row
    raise KeyError(kind)


def least_seconds(kind: str, bh: int, t: int, d: int, peak_flops: float,
                  peak_bytes: float, item: int = 2):
    """(seconds, bound) — the larger of the compute and memory bounds."""
    tc = call_flops(kind, bh, t, d) / peak_flops
    tm = call_bytes(kind, bh, t, d, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def calls_per_step(remat: bool) -> dict:
    """Executed kernel calls per layer per local training step: the forward
    runs again on the backward pass under remat."""
    return {"fwd": 2 if remat else 1, "dq": 1, "dkv": 1}
