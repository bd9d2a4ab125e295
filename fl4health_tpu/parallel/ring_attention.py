"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has NO long-context machinery (SURVEY §5 "Long-context /
sequence parallelism: absent"): its BERT/LLM examples run standard attention
and delegate scale to DeepSpeed configs. The task brief makes long-context a
first-class TPU concern, so this module provides the canonical TPU recipe:
blockwise ring attention (Liu et al., "Ring Attention with Blockwise
Transformers") — the sequence axis is sharded over a ``seq`` mesh axis; each
device holds one query block and streams key/value blocks around the ring
with ``lax.ppermute`` over ICI, maintaining an online-softmax accumulator
(flash-attention state: running max, normalizer, weighted sum). Peak memory
per device is O(T/N * T/N) attention scores instead of O(T^2); the K/V
transfers overlap the block matmuls on real hardware.

Semantics: exact (not approximate) softmax attention — the ring test asserts
bitwise-level agreement (atol 1e-5) with dense attention on a virtual mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _dense_attention(q, k, v, pad_mask=None):
    """Reference dense softmax attention. q,k,v: [B, T, H, D];
    pad_mask: [B, T] with 1 = real token. Used for tests and as the
    single-device fallback."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if pad_mask is not None:
        scores = jnp.where(pad_mask[:, None, None, :] > 0, scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _dense_local_lse(q_blk, k_blk, v_blk, mask_blk):
    """Dense local block returning (out, lse) — the partial-attention pair
    the ring driver merges. lse for an all-masked row is ~NEG_INF (large
    FINITE negative, mirroring the flash kernel's contract) so the merge
    algebra never sees inf-inf."""
    d = q_blk.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale
    )
    scores = jnp.where(mask_blk[:, None, None, :] > 0, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(mask_blk[:, None, None, :] > 0, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    denom = jnp.maximum(l, 1e-20)
    lse = m + jnp.log(denom)
    # stay fp32: the ring driver accumulates in fp32 and casts ONCE at the
    # end, so the DENSE ring adds no per-hop quantization. (The flash local
    # block is different: its kernel writes each hop's output in the io
    # dtype — inherent to its memory layout — so bf16 ring-flash carries
    # one io-dtype rounding per hop into the fp32 merge.)
    return o / denom[..., None].transpose(0, 2, 1, 3), lse


def _ring_body(q_blk, k_blk, v_blk, mask_blk, local_fn, axis_name: str):
    """Shared ring driver (shard_map body): the local [B, Tq, H, D] query
    block attends over all key blocks as they rotate around the ring via
    ``ppermute``. ``local_fn(q, k, v, mask) -> (out, lse)`` computes one
    block's exact partial attention; hops merge through the logsumexp
    identity (running max M, normalizer S, weighted numerator ACC — the
    online-softmax algebra one level up), so the driver is the ONE copy of
    the rotation/merge logic for both the dense and the flash local block.
    """
    ring = jax.lax.axis_size(axis_name)
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    # local block first, then n-1 hops: rotate-THEN-compute so no transfer's
    # result is ever discarded (n hops would waste 3 collectives per call).
    o0, lse0 = local_fn(q_blk, k_blk, v_blk, mask_blk)
    m0 = lse0  # [B, H, Tq]
    s0 = jnp.ones_like(lse0)
    acc0 = o0.astype(jnp.float32)

    def hop(_, carry):
        acc, m, s, k_cur, v_cur, mask_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_cur = jax.lax.ppermute(mask_cur, axis_name, perm)
        o_j, lse_j = local_fn(q_blk, k_cur, v_cur, mask_cur)
        m_new = jnp.maximum(m, lse_j)
        c = jnp.exp(m - m_new)      # rescale old accumulators
        w = jnp.exp(lse_j - m_new)  # weight of this hop
        s = s * c + w
        cw = jnp.transpose(c, (0, 2, 1))[..., None]
        ww = jnp.transpose(w, (0, 2, 1))[..., None]
        acc = acc * cw + ww * o_j.astype(jnp.float32)
        return acc, m_new, s, k_cur, v_cur, mask_cur

    acc, m, s, _, _, _ = jax.lax.fori_loop(
        0, ring - 1, hop, (acc0, m0, s0, k_blk, v_blk, mask_blk)
    )
    denom = jnp.maximum(jnp.transpose(s, (0, 2, 1))[..., None], 1e-20)
    return (acc / denom).astype(q_blk.dtype)


def _ring_shard_map(local_fn, mesh, axis_name, q, k, v, pad_mask):
    qkv_spec = P(None, axis_name, None, None)
    mask_spec = P(None, axis_name)
    fn = jax.shard_map(
        functools.partial(_ring_body, local_fn=local_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, pad_mask)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "seq",
    pad_mask: jax.Array | None = None,
) -> jax.Array:
    """Exact softmax attention with the sequence axis sharded over
    ``axis_name``. q,k,v: [B, T, H, D] global arrays (T divisible by the axis
    size); pad_mask: [B, T] (1 = token). Returns [B, T, H, D] sharded the
    same way.
    """
    if pad_mask is None:
        pad_mask = jnp.ones(q.shape[:2], jnp.float32)
    return _ring_shard_map(_dense_local_lse, mesh, axis_name, q, k, v,
                           pad_mask)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "seq",
    pad_mask: jax.Array | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Ring attention whose LOCAL block is the Pallas flash kernel — the
    full long-context recipe: the sequence axis shards over ``axis_name``
    (ring hops via ``ppermute``), and within each hop the [Tq/N, Tk/N]
    block runs through ``kernels.flash_attention_lse`` so the block score
    matrix never touches HBM either. Per-hop partials merge exactly via the
    logsumexp statistic: ``L = max_j lse_j`` running-max, weights
    ``exp(lse_j - L)`` — the same online-softmax algebra as the dense ring,
    one level up. Differentiable end-to-end (lse carries a first-class
    cotangent through the kernel's custom VJP).

    Same contract as ring_self_attention; additionally the local length T/N
    must be divisible by usable block sizes: each block shrinks to
    gcd(T/N, block) and a degenerate shrink (below 8 on a real-sized
    shard) raises rather than compiling a pathological Mosaic tile. On the
    TPU the shrunk blocks must also pass the kernel's own check (multiples
    of 128 or the whole local length) — pick T/N divisible by the blocks.
    """
    import math as _math

    from fl4health_tpu.kernels.flash_attention import flash_attention_lse

    if pad_mask is None:
        pad_mask = jnp.ones(q.shape[:2], jnp.float32)
    n = mesh.shape[axis_name]
    t_local = q.shape[1] // n
    # Each block shrinks independently to a divisor of the local length
    # (lcm of two divisors of t_local still divides it). A degenerate
    # shrink (< 8 on a real-sized shard) is an error, not a silent
    # pathological Mosaic tile — pick T and block sizes that agree.
    bq, bk = _math.gcd(t_local, block_q), _math.gcd(t_local, block_k)
    if min(bq, bk) < 8 and t_local >= 8:
        raise ValueError(
            f"ring_flash_attention: local length {t_local} is incompatible "
            f"with block sizes ({block_q}, {block_k}) — the divisor shrink "
            f"degenerates to ({bq}, {bk}); choose T/N divisible by the "
            "block sizes"
        )

    def local(q_blk, k_cur, v_cur, mask_cur):
        return flash_attention_lse(
            q_blk, k_cur, v_cur, mask_cur,
            block_q=bq, block_k=bk, interpret=interpret,
        )

    return _ring_shard_map(local, mesh, axis_name, q, k, v, pad_mask)


def sequence_parallel_sharding(mesh: Mesh, axis_name: str = "seq"):
    """NamedSharding placing [B, T, ...] activations with T over the seq
    axis — the placement companion for feeding ring attention."""
    return NamedSharding(mesh, P(None, axis_name))
