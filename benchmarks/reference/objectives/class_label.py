"""The class-label objective, plain side: one label a row, computed from the
row, and the mean cross entropy of the model's ``[B, classes]`` logits
against it. jax.numpy only; imports nothing of the program.

An objective's plain side is two functions, found by the name in the traffic
file's ``objective`` entry (``defaults.json`` where it has none):

``targets(key, x, lengths, inp, objective) -> (x, y)``
    what both sides start from, called by ``harness/datagen.make_data``
    inside its one jitted call. ``key`` is the targets' own key, folded from
    ``--seed``: whatever an objective draws at random it draws here, with the
    data, so the program and the reference see the same draw. ``x`` is the
    inputs as drawn (tokens ``[C, n, seq]`` int32, 0 beyond a row's length;
    images ``[C, n, hw, hw, ch]`` of noise), ``lengths`` ``[C, n, 1]`` the
    tokens' (``None`` for images), ``inp`` the reference family's
    ``input_spec``, ``objective`` the traffic file's entry. Both results keep
    ``[C, n]`` as their first two axes; the rest is the objective's: ``x``
    may come back with an axis more than it went in with, ``y`` in any shape
    and type.

``loss(out, x, y, nm) -> scalar``
    a step's loss from the reference family's ``forward(p, x, nm)`` output
    on a batch ``x`` ``[B, ...]``, ``y`` ``[B, ...]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def targets(key, x, lengths, inp: dict, objective: dict):
    classes = int(inp["classes"])
    if inp["kind"] == "tokens":
        # the label is a function of the text, so the task is learnable
        return x, ((x[..., 0] + x[..., 1]) % classes).astype(jnp.int32)
    # images: a label a row, and the noise as drawn moved towards the
    # label's pattern, so the task is learnable
    n_clients, n, hw, _, ch = x.shape
    k_y, k_pat = jax.random.split(key)
    y = jax.random.randint(k_y, (n_clients, n), 0, classes, jnp.int32)
    shards = int(inp.get("label_shards") or 0)
    if shards:
        # label-sorted shards (McMahan et al. 2017's non-IID split):
        # a client holds ``shards`` consecutive classes, and the
        # classes follow the client's index through the cohort
        first = (jnp.arange(n_clients) * classes) // n_clients
        y = (first[:, None] + y % shards) % classes
    pattern = jax.random.normal(k_pat, (classes, hw, hw, ch), jnp.float32)
    return x + 0.5 * pattern[y], y.astype(jnp.int32)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss(out, x, y, nm):
    return cross_entropy(out, y)
