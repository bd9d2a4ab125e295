"""Operations and bytes that one call of each flash-attention kernel needs
under a sliding window over grouped-query heads (kernels/flash_attention.py,
``window`` on the ``grouped`` addressing): ``heads`` query heads of ``d``
lanes over ``kv_heads`` key/value heads, ``b`` sequences of ``t`` positions;
of the t x t scores those INSIDE the window, ``w*t - w*(w-1)/2`` a head
(query i sees itself and the min(i, w - 1) keys before it; the causal
triangle where w >= t), not the tiles the kernels execute (70 of the causal
136 at T 8,192, W 2,048 and blocks of 512, of which 16 hold the diagonal and
12 the window's far edge): what a tile's masked scores cost shows as a lower
share, and a kernel that skipped fewer tiles, or none, would read lower still.

Dots per call, query head and sequence, each 2*d a score, and the least
bytes a call must move are ``flops/gqa_flash.py``'s (``DOTS``,
``call_bytes``: forward 2 dots, dQ 3, dK/dV 4; q, the output, dO and dQ at
``heads`` heads, k / v and their gradients at ``kv_heads``). The window
spares no byte: every position is a query and a key.
"""

from __future__ import annotations

import os


def _gqa():
    from benchmarks.harness.spec import load_module

    return load_module("flops", "gqa_flash", os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def scores_in_window(t: int, window: int) -> float:
    """Scores a query head requires: the band, or the triangle under it."""
    w = min(window, t)
    return w * t - w * (w - 1) / 2.0


def call_flops(kind: str, b: int, t: int, heads: int, d: int,
               window: int) -> float:
    return (_gqa().DOTS[kind] * 2.0 * scores_in_window(t, window) * d * heads
            * b)


def least_seconds(kind: str, b: int, t: int, heads: int, kv_heads: int,
                  d: int, window: int, peak_flops: float, peak_bytes: float,
                  item: int = 2):
    """(seconds, bound): the larger of the in-window scores' FLOPs at the
    MXU peak and the least bytes at the HBM peak."""
    tc = call_flops(kind, b, t, heads, d, window) / peak_flops
    tm = _gqa().call_bytes(kind, b, t, heads, kv_heads, d, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def least_seconds_of_calls(cfg: dict, job: dict, calls: dict,
                           peak_flops: float, peak_bytes: float) -> float:
    """``calls``: kernel kind -> executed calls (what a trace SHOWS; a call
    covers every client's batch: the client axis is a grid axis)."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    b = int(job["clients"]) * int(job["batch"])
    return sum(n * least_seconds(
        kind, b, int(job["data"]["seq"]), cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["sliding_window"],
        peak_flops, peak_bytes, item)[0] for kind, n in calls.items())
