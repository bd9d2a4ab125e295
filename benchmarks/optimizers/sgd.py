"""Adapter: the clients' optimizer as the program takes it (an optax
transformation), from the traffic file's ``optimizer`` entry."""

from __future__ import annotations


def build_tx(opt: dict):
    import optax

    return optax.sgd(float(opt["lr"]))
