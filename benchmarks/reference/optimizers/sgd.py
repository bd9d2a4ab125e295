"""Plain SGD for the reference: ``p <- p - lr * g``, no state."""

from __future__ import annotations

import jax


def init(params: dict, opt: dict):
    return ()


def update(params: dict, grads: dict, state, opt: dict):
    lr = float(opt["lr"])
    return jax.tree_util.tree_map(lambda a, b: a - lr * b, params, grads), state
