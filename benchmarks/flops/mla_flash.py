"""Operations and bytes that one call of each flash-attention kernel needs
at latent attention's two head widths (kernels/flash_attention.py with a
value width of its own), over ``bh`` (batch x heads) rows of ``t`` positions;
``dqk`` lanes for q and k, ``dv`` for v, the output and dO: the widths the
mathematics has (192 and 128), not what the wrapper pads them to (256 and
128), and of the t x t scores the causal triangle, not the tiles the kernels
execute (3 of 4 at T 1,024 with blocks of 512). What padding and the tiles
above the diagonal cost shows as a lower share.

Dots per call and row, each 2*t*t*width dense:
  forward:  S = Q K^T at dqk, O = P V at dv
  dQ:       S (recomputed) at dqk, dP = dO V^T at dv, dQ = dS K at dqk
  dK/dV:    S (recomputed) at dqk, dV = P^T dO at dv, dP = dO V^T at dv,
            dK = dS^T Q at dqk
Bytes are the least the call must move: every operand read once, every
result written once (``item`` bytes for q/k/v/o/do/dq/dk/dv, 4 for the mask,
lse and delta rows).
"""

from __future__ import annotations

import os

# (dots at the query/key width, dots at the value width)
DOTS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}
# (arrays at the query/key width, arrays at the value width, float32 rows)
ARRAYS = {"fwd": (2, 2, 2), "dq": (3, 2, 3), "dkv": (4, 3, 3)}


def causal_fraction(t: int) -> float:
    """Share of the t x t scores on or under the diagonal."""
    return (t + 1) / (2.0 * t)


def call_flops(kind: str, bh: int, t: int, dqk: int, dv: int) -> float:
    n_qk, n_v = DOTS[kind]
    return 2.0 * t * t * bh * (n_qk * dqk + n_v * dv)


def call_bytes(kind: str, bh: int, t: int, dqk: int, dv: int,
               item: int = 2) -> float:
    n_qk, n_v, rows = ARRAYS[kind]
    return bh * t * ((n_qk * dqk + n_v * dv) * item + rows * 4.0)


def least_seconds(kind: str, bh: int, t: int, dqk: int, dv: int,
                  peak_flops: float, peak_bytes: float, item: int = 2,
                  executed: float = 1.0):
    """(seconds, bound): the larger of the compute and memory bounds;
    ``executed`` is the share of the t x t scores the mask leaves."""
    tc = executed * call_flops(kind, bh, t, dqk, dv) / peak_flops
    tm = call_bytes(kind, bh, t, dqk, dv, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def least_seconds_per_round(cfg: dict, job: dict, peak_flops: float,
                            peak_bytes: float) -> float:
    """Every training call of a round: clients x local steps x layers x the
    calls a step makes (``flops/flash_attention.calls_per_step``: the forward
    runs again on the backward pass under remat). The evaluation forwards are
    left out, so the least time is, if anything, too small and the share too
    low."""
    from benchmarks.harness.spec import load_module

    per_step = load_module("flops", "flash_attention", os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))).calls_per_step(
        bool(job.get("remat")))
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    t = int(job["data"]["seq"])
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    bh = int(job["batch"]) * cfg["num_attention_heads"]
    executed = causal_fraction(t)
    one_step = sum(n * least_seconds(kind, bh, t, dqk, dv, peak_flops,
                                     peak_bytes, item, executed)[0]
                   for kind, n in per_step.items())
    steps = int(job["clients"]) * int(job["local_steps"])
    return one_step * cfg["num_hidden_layers"] * steps
