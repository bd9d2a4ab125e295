"""Token denoising by blocks, plain side (``reference/objectives/
class_label.py`` has the contract). Per block of ``block_length`` positions
one masking level t, uniform in [``t_min``, ``t_max``]; a real position is
masked (its id replaced by ``mask_id``) where its own uniform draw lies under
its block's t. ``x`` comes back ``[C, n, 2, seq]`` int32, row 0 noised and row
1 clean; ``y`` ``[C, n, seq]`` float32 is 1/t on masked real positions and 0
elsewhere. The loss is the sum of y times the token cross entropy against the
clean ids over a batch's real positions, divided by their count."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def targets(key, x, lengths, inp: dict, objective: dict):
    block = int(objective["block_length"])
    n_clients, n, seq = x.shape
    if seq % block:
        raise ValueError(f"seq {seq} is not whole blocks of {block}")
    k_t, k_u = jax.random.split(key)
    t = jax.random.uniform(k_t, (n_clients, n, seq // block), jnp.float32,
                           float(objective["t_min"]), float(objective["t_max"]))
    t = jnp.repeat(t, block, axis=-1)
    real = jnp.arange(seq)[None, None, :] < lengths
    masked = real & (jax.random.uniform(k_u, x.shape, jnp.float32) < t)
    noised = jnp.where(masked, jnp.int32(objective["mask_id"]), x)
    return (jnp.stack([noised, x], axis=2),
            jnp.where(masked, 1.0 / t, 0.0).astype(jnp.float32))


def loss(out, x, y, nm):
    clean = x[:, 1]
    logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
    real = (clean > 0).astype(jnp.float32)
    return jnp.sum(y * nll * real) / jnp.sum(real)
