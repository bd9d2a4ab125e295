"""Remat by named residuals: what a rematerialised layer keeps.

A layer under ``jax.checkpoint`` runs its whole forward again on the way
back. Some of what it would recompute is dear per byte kept (a flash
forward: 4·T·T·D FLOPs for T·D·2 bytes of ``out``), so the code that
computes such an array gives it a name (``named``) and a remat site that
wants it kept says so (``keep``): ``jax.checkpoint(body, policy=keep(NAMES))``
saves the named arrays where they are first computed and recomputes
everything else. Neither half does anything alone: a name no policy asks for
is the identity (it lowers to nothing), a policy whose names nothing carries
saves nothing.

What a site keeps is a constant of its model family, stated beside the site;
this module holds the mechanism and the build-time count of it only.
"""

from __future__ import annotations

import contextlib

import jax
from jax.ad_checkpoint import checkpoint_name

# {name: bytes} dicts of the counters open now
_open_counters: list = []


@contextlib.contextmanager
def count_named():
    """Records, while open, every array TRACED through ``named``: ``{name:
    bytes of one array of that name}`` (the largest, where one name is given
    to arrays of several sizes; a run of layers under ``lax.scan`` traces
    its layer once, and a retrace changes nothing). Under a ``vmap`` the
    bytes are one batch member's."""
    seen: dict = {}
    _open_counters.append(seen)
    try:
        yield seen
    finally:
        _open_counters[:] = [c for c in _open_counters if c is not seen]


def named(x: jax.Array, name: str) -> jax.Array:
    """``x`` under ``name`` for the remat policies (``keep``); the identity
    wherever no policy asks for the name."""
    for seen in _open_counters:
        seen[name] = max(seen.get(name, 0), x.size * x.dtype.itemsize)
    return checkpoint_name(x, name)


def keep(names):
    """The ``jax.checkpoint`` policy that saves the arrays ``named`` one of
    ``names`` and nothing else."""
    return jax.checkpoint_policies.save_only_these_names(*names)


def saved_gauges(forward, args, names, n_clients: int) -> dict:
    """What a family's remat sites keep, as facts of the build:
    ``remat_saved_names`` (how many of ``names`` the differentiated forward
    carries) and ``remat_saved_bytes_per_layer`` (one array of each, over
    ``n_clients`` clients: what one layer that keeps them adds to the round
    program's residuals). ``forward(*args)`` is differentiated abstractly
    (the names a kernel gives lie in its VJP's forward rule): nothing is
    allocated or run. ``names`` empty (no remat): zeros, nothing traced."""
    seen: dict = {}
    if names:
        with count_named() as seen:
            jax.eval_shape(lambda *a: jax.vjp(forward, *a)[0], *args)
    kept = {k: v for k, v in seen.items() if k in names}
    return {"remat_saved_names": len(kept),
            "remat_saved_bytes_per_layer": n_clients * sum(kept.values())}
