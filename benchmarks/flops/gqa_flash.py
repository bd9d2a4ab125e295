"""Operations and bytes that one call of each flash-attention kernel needs
under grouped-query heads (kernels/flash_attention.py, the ``grouped``
addressing): ``heads`` query heads of ``d`` lanes over ``kv_heads`` key/value
heads, ``b`` sequences of ``t`` positions; of the t x t scores the causal
triangle, not the tiles the kernels execute (5 of 8 at T 2,048 with blocks
of 512): what the tiles above the diagonal cost shows as a lower share.

Dots per call, query head and sequence, each 2*t*t*d dense:
  forward 2 (S = Q K^T, O = P V); dQ 3 (S, dP, dQ = dS K);
  dK/dV 4 (S, dV = P^T dO, dP, dK = dS^T Q)
Bytes are the least the call must move: q, the output, dO and dQ at ``heads``
heads, k / v and their gradients at ``kv_heads`` (a key/value head is read
once for the group that shares it), ``item`` bytes each; 4 for the mask and
for the lse and delta rows a query head.
"""

from __future__ import annotations

DOTS = {"fwd": 2, "dq": 3, "dkv": 4}
# (arrays at the query heads, arrays at the key/value heads, float32 rows a
# query head)
ARRAYS = {"fwd": (2, 2, 1), "dq": (3, 2, 2), "dkv": (2, 4, 2)}


def causal_fraction(t: int) -> float:
    """Share of the t x t scores on or under the diagonal."""
    return (t + 1) / (2.0 * t)


def call_flops(kind: str, b: int, t: int, heads: int, d: int) -> float:
    return DOTS[kind] * 2.0 * t * t * d * heads * b


def call_bytes(kind: str, b: int, t: int, heads: int, kv_heads: int, d: int,
               item: int = 2) -> float:
    n_q, n_kv, rows = ARRAYS[kind]
    return b * t * ((n_q * heads + n_kv * kv_heads) * d * item
                    + (rows * heads + 1) * 4.0)


def least_seconds(kind: str, b: int, t: int, heads: int, kv_heads: int,
                  d: int, peak_flops: float, peak_bytes: float,
                  item: int = 2):
    """(seconds, bound): the larger of the causal triangle's FLOPs at the
    MXU peak and the least bytes at the HBM peak."""
    tc = causal_fraction(t) * call_flops(kind, b, t, heads, d) / peak_flops
    tm = call_bytes(kind, b, t, heads, kv_heads, d, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def least_seconds_of_calls(cfg: dict, job: dict, calls: dict,
                           peak_flops: float, peak_bytes: float) -> float:
    """``calls``: kernel kind -> executed calls (what a trace SHOWS; a call
    covers every client's batch: the client axis is a grid axis)."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    b = int(job["clients"]) * int(job["batch"])
    return sum(n * least_seconds(
        kind, b, int(job["data"]["seq"]), cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], peak_flops, peak_bytes,
        item)[0] for kind, n in calls.items())
