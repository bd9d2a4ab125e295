"""Operations and bytes that one executed pass of the chunked scalar-decay
scan (kernels/ssd_scan.py: Mamba-2's recurrence as matmuls over chunks of
``chunk_size`` positions) needs, per token, for ``H`` heads of ``P`` in ``G``
groups with a state of ``N``.

Matmul FLOPs of the forward, per token (whole Q x Q tiles: the chunked form
computes them whole and masks, a tile's causal half is not skipped):
  C B^T            2*Q*N a group
  scores (dt x)    2*Q*P a head
  the chunk state  2*P*N a head
  C S_in           2*N*P a head
The backward of a matmul is two matmuls of its size: 2 x the forward. The
decays' exponentials (H*Q a token), the masks and the elementwise products
are vector work that ``peaks.json`` has no peak for; they are left out, so
the compute bound is, if anything, too small.

Bytes are the least a pass must move: the Q x Q tiles and the states never
need to leave the chip. Forward: reads x (H*P), B and C (G*N each) at the
compute type and dt (H, float32), writes y (H*P, float32). Backward: reads
those and dy, writes dx, dB, dC at the compute type and d(dt) in float32.
"""

from __future__ import annotations


def sizes(cfg: dict):
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["chunk_size"])


def forward_flops_per_token(cfg: dict) -> float:
    h, p, g, n, q = sizes(cfg)
    return 2.0 * q * n * g + h * (2.0 * q * p + 2.0 * p * n + 2.0 * n * p)


def pass_flops_per_token(kind: str, cfg: dict) -> float:
    return {"fwd": 1.0, "bwd": 2.0}[kind] * forward_flops_per_token(cfg)


def pass_bytes_per_token(kind: str, cfg: dict, item: int = 2) -> float:
    h, p, g, n, _ = sizes(cfg)
    fwd = (h * p + 2 * g * n) * item + h * 4 + h * p * 4
    if kind == "fwd":
        return float(fwd)
    if kind == "bwd":  # the forward's reads, dy, and the four gradients
        return float(fwd + (h * p + 2 * g * n) * item + h * 4)
    raise KeyError(kind)


def least_seconds(kind: str, tokens: int, cfg: dict, peak_flops: float,
                  peak_bytes: float, item: int = 2):
    """(seconds, bound) of one pass over ``tokens`` positions: the larger of
    the matmuls at the MXU peak and the least bytes at the HBM peak."""
    tc = tokens * pass_flops_per_token(kind, cfg) / peak_flops
    tm = tokens * pass_bytes_per_token(kind, cfg, item) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def mamba_blocks(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]].count("M")


def least_seconds_per_round(cfg: dict, job: dict, peak_flops: float,
                            peak_bytes: float, passes) -> float:
    """The training passes of a round that ``passes`` names (the passes a
    trace SHOWS under the scope: ``forward`` and ``recompute`` each cost a
    forward, ``backward`` a backward), each clients x local steps x Mamba
    blocks times. The evaluation forwards are in neither this nor the time
    it is held against."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["compute_dtype"]]
    tokens = int(job["batch"]) * int(job["data"]["seq"])
    kinds = {"forward": "fwd", "recompute": "fwd", "backward": "bwd"}
    one = sum(least_seconds(kinds[p], tokens, cfg, peak_flops, peak_bytes,
                            item)[0] for p in passes if p in kinds)
    return (one * mamba_blocks(cfg) * int(job["clients"])
            * int(job["local_steps"]))
