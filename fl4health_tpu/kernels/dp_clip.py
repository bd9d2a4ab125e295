"""Pallas kernels for the DP-SGD hot path — fused per-example clip + reduce.

The XLA path (privacy/dpsgd.py) makes three full passes over the [B, D]
per-example gradient tensor: (1) squared-norm reduction, (2) scale-and-write
the clipped tensor, (3) masked sum over B. Passes 2+3 materialize and then
re-read a [B, D] intermediate — pure HBM bandwidth, the dominant cost for
big models (D ~ 10^6-10^8 per batch). These kernels do it in TWO passes and
never materialize the clipped tensor:

    pass 1  sq_norms:   per leaf [B, W] -> [B]  (tiled over W, summed
                                                 across leaves)
    pass 2  scaled sum: per leaf [B, W] -> [W]  (clip scale folded in)

Both kernels tile D into lane-aligned blocks with the whole batch resident
per block (B is small in DP training; the [B, TILE] block fits VMEM). On
the CPU backend the kernels run in Pallas interpret mode, so the same code
path is exercised by the CPU test suite; `fused_clipped_masked_sum` is the
drop-in used by privacy.dpsgd when enabled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from fl4health_tpu.core.types import Params
from fl4health_tpu.kernels._platform import interpret_default
from fl4health_tpu.observability import stages as stage_attr

_LANE = 128


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Pass 1: per-example squared norms
# ---------------------------------------------------------------------------

def _sq_norm_kernel(g_ref, out_ref):
    i = pl.program_id(0)
    partial = jnp.sum(jnp.square(g_ref[:].astype(jnp.float32)), axis=1,
                      keepdims=True)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = partial

    @pl.when(i > 0)
    def _acc():
        out_ref[:] += partial


def _effective_tile(width: int, tile: int) -> int:
    """Clamp the tile to the leaf's lane-rounded width: a [B, 10] bias pads
    to one 128-lane tile, not a full 2048 — small leaves must not reduce
    thousands of zero columns per pass."""
    return min(tile, max(_LANE, -(-width // _LANE) * _LANE))


def per_example_sq_norms(
    flat_grads: jax.Array, tile: int = 2048, interpret: bool | None = None
) -> jax.Array:
    """[B, D] -> [B] squared l2 norms, one pass, D tiled."""
    if interpret is None:
        interpret = interpret_default()
    b, d = flat_grads.shape
    tile = _effective_tile(d, tile)
    g = _pad_to(flat_grads, 1, tile)
    n_tiles = g.shape[1] // tile
    out = pl.pallas_call(
        _sq_norm_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((b, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((b, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
    )(g)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Pass 2: scaled masked sum (the clipped tensor never exists)
# ---------------------------------------------------------------------------

def _scaled_sum_kernel(scale_ref, g_ref, out_ref):
    out_ref[:] = jnp.sum(
        g_ref[:].astype(jnp.float32) * scale_ref[:].astype(jnp.float32),
        axis=0, keepdims=True,
    )


def scaled_masked_sum(
    flat_grads: jax.Array, scale: jax.Array, tile: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """sum_i scale[i] * g[i]  ([B, D], [B] -> [D]), one pass, D tiled."""
    if interpret is None:
        interpret = interpret_default()
    b, d = flat_grads.shape
    tile = _effective_tile(d, tile)
    g = _pad_to(flat_grads, 1, tile)
    n_tiles = g.shape[1] // tile
    out = pl.pallas_call(
        _scaled_sum_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((b, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, g.shape[1]), jnp.float32),
        interpret=interpret,
    )(scale[:, None], g)
    return out[0, :d]


# ---------------------------------------------------------------------------
# The fused DP reduction over a gradient pytree
# ---------------------------------------------------------------------------

def fused_clipped_masked_sum(
    per_example_grads: Params,
    example_mask: jax.Array,
    clipping_bound: float,
    tile: int = 2048,
    interpret: bool | None = None,
    return_norms: bool = False,
) -> Params:
    """sum_i mask[i] * min(1, C/||g_i||) * g_i over a [B,...]-leaved pytree,
    without materializing the clipped per-example tensor (the fused
    replacement for dpsgd.clip_per_example + masked sum).

    ``return_norms=True`` additionally returns the pre-clip per-example
    norms [B] — pass 1 already computes them, so exporting costs nothing
    extra; the DP telemetry derives its clip fraction
    (``mean(mask * [norm > C])``) from this without a third pass.

    Kernels run PER LEAF on [B, leaf_width] views (reshape of a contiguous
    leaf is metadata, not a copy) with the squared-norm partials accumulated
    across leaves — concatenating the tree into one [B, D] matrix first
    would itself write+read the full tensor and forfeit the bandwidth win.
    Leaf sums come back f32 regardless of input dtype (the XLA path promotes
    via the f32 mask multiply, and DP noise must be added at full precision).
    """
    with stage_attr.stage("dp_clip"):
        leaves, treedef = jax.tree_util.tree_flatten(per_example_grads)
        mats = [leaf.reshape(leaf.shape[0], -1) for leaf in leaves]

        sq = sum(
            per_example_sq_norms(m, tile=tile, interpret=interpret)
            for m in mats
        )
        norms = jnp.sqrt(jnp.maximum(sq, 0.0))
        factor = jnp.minimum(1.0, clipping_bound / jnp.maximum(norms, 1e-12))
        scale = factor * example_mask.astype(jnp.float32)

        sums = [
            scaled_masked_sum(m, scale, tile=tile, interpret=interpret)
            .reshape(leaf.shape[1:])
            for leaf, m in zip(leaves, mats)
        ]
        out = jax.tree_util.tree_unflatten(treedef, sums)
    if return_norms:
        return out, norms
    return out
