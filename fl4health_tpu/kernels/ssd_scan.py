"""The scalar-decay state-space recurrence (Mamba-2's "SSD": Dao & Gu 2024,
arXiv:2405.21060) in its chunked form: matmuls on the MXU, no Mosaic kernel.

The recurrence, for head ``h`` of group ``g = h // (H / G)`` with ONE decay
``a_h < 0`` a head and a state ``S in R^{P x N}``::

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(``x_t in R^P``, ``B_t``, ``C_t in R^N`` shared by the heads of a group,
``dt_t > 0`` a head). Over a chunk of ``Q`` positions, with ``cum_t`` the
running sum of ``dt a`` inside the chunk:

- inside the chunk ``Y = (L o C B^T) (dt x)``, ``L_ts = exp(cum_t - cum_s)``
  for ``s <= t`` and 0 above the diagonal: one ``[Q, Q]`` score tile a group
  (``C B^T``), decayed per head, times the chunk's ``[Q, P]`` inputs;
- the chunk's own state ``sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T``, one
  ``[R * P, Q] x [Q, N]`` product a group (``R = H / G`` heads);
- the states carried across the chunks, ``S_in(c + 1) = exp(cum_Q(c)) S_in(c)
  + state(c)``: ``T / Q`` elementwise steps of a ``lax.scan``;
- what the incoming state adds, ``exp(cum_t) C_t S_in``: one ``[Q, N] x [N,
  R * P]`` product a group.

The products take their operands in ``x``'s type (the compute type) and
accumulate in float32; ``dt``, the decays (``cum``, ``L``, every ``exp``) and
the carried state are float32 whatever the operands are. The exponent above
the diagonal is masked BEFORE ``exp`` (it is positive there and may be
large). Plain ``jnp`` / ``lax``: it runs under ``vmap``, ``jax.checkpoint``
and ``grad`` as it is. The mixer is causal and pad positions sit at the tail,
so no mask enters; a length that is no multiple of the chunk is padded with
``dt = 0`` (a step that neither decays nor adds) and cut again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fl4health_tpu.observability.stages import layer as part

F32 = jnp.float32


def n_chunks(t: int, chunk: int) -> int:
    return -(-t // chunk)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """x [B, T, H, P] (the compute type), dt [B, T, H] float32 (after
    softplus), a [H] float32 (negative), b / c [B, T, G, N] -> y [B, T, H, P]
    float32, ``y_t = S_t C_t`` of the recurrence above (the caller adds the
    skip ``D x``)."""
    with part("ssd_scan"):
        bsz, t, h, p = x.shape
        g, n = b.shape[2:]
        r, dtype = h // g, x.dtype
        pad = -t % chunk
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                   * (v.ndim - 2)) for v in (x, dt, b, c))
        nc = (t + pad) // chunk
        x = x.reshape(bsz, nc, chunk, g, r, p)
        dt = dt.astype(F32).reshape(bsz, nc, chunk, g, r)
        b = b.reshape(bsz, nc, chunk, g, n)
        c = c.reshape(bsz, nc, chunk, g, n)
        # cum_t = sum_{r <= t} dt_r a: [B, nc, Q, G, R], float32
        cum = jnp.cumsum(dt * a.astype(F32).reshape(g, r), axis=2)
        cum_h = jnp.transpose(cum, (0, 1, 3, 4, 2))  # [B, nc, G, R, Q]
        on_or_under = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
        decay = jnp.exp(jnp.where(
            on_or_under, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", c, b,
                        preferred_element_type=F32)
        scores = (decay * cb[:, :, :, None]).astype(dtype)
        xf = x.astype(F32)
        xdt = (xf * dt[..., None]).astype(dtype)
        y = jnp.einsum("bcgrts,bcsgrp->bctgrp", scores, xdt,
                       preferred_element_type=F32)
        # the chunk's own state and what the chunk leaves of an older one
        to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, Q, G, R]
        xw = (xf * (dt * to_end)[..., None]).astype(dtype)
        states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, b,
                            preferred_element_type=F32)
        total = jnp.exp(cum[:, :, -1])  # [B, nc, G, R]

        def carry(s_in, chunk_c):
            state, keep = chunk_c
            return s_in * keep[..., None, None] + state, s_in

        _, s_in = jax.lax.scan(
            carry, jnp.zeros((bsz, g, r, p, n), F32),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
        s_in = jnp.moveaxis(s_in, 0, 1).astype(dtype)  # [B, nc, G, R, P, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bctgn,bcgrpn->bctgrp", c, s_in, preferred_element_type=F32)
        return y.reshape(bsz, t + pad, h, p)[:, :t]
