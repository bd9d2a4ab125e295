"""Plain FedAvg over the trainable leaves of a model whose base is frozen and
held once: the semantics ``strategies/fedavg_adapters`` cells are held to.

As ``fedavg.py`` (same rounds, same shuffle, same weighted mean, same
losses), except that a leaf is trained, sent and averaged only if a whole
segment of its path is one of the traffic file's ``strategy.trainable``
names. Every other leaf keeps its seeded value for ever: it is an argument of
the step, never differentiated, never stacked over clients, never averaged
(``fedavg.py`` would hold a gradient and a client stack of every leaf: 6.4 GB
a copy of this base), and its reported change is exactly 0.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.spec import load_module

_fedavg = load_module("reference/strategies", "fedavg", os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def split(params: dict, trainable_names) -> tuple[dict, dict]:
    """(trainable leaves, frozen leaves) by whole path segments."""
    names = set(trainable_names)
    train = {k: v for k, v in params.items() if names & set(k.split("/"))}
    return train, {k: v for k, v in params.items() if k not in train}


def make_block_fn(forward, loss, opt_mod, opt: dict, nm):
    """jitted (trainable, frozen, states [Cb,...], xb [Cb,S,B,...],
    yb [Cb,S,B,...], w [Cb]) -> (sum_i w_i * trainable_i, sum_i w_i * loss_i,
    new states)."""

    def client(train, frozen, state, xb, yb):
        def step(carry, batch):
            p, state = carry
            x, y = batch
            value, g = jax.value_and_grad(lambda q: loss(
                forward({**frozen, **q}, x, nm), x, y, nm))(p)
            p, state = opt_mod.update(p, g, state, opt)
            return (nm.master(p), state), value
        (p, state), losses = jax.lax.scan(step, (train, state), (xb, yb))
        return p, state, jnp.mean(losses)

    @jax.jit
    def block(train, frozen, states, xb, yb, w):
        ps, states, losses = jax.vmap(client, in_axes=(None, None, 0, 0, 0))(
            train, frozen, states, xb, yb)
        wsum = jax.tree_util.tree_map(
            lambda a: jnp.tensordot(w, a, axes=1), ps)
        return wsum, jnp.sum(w * losses), states

    return block


def run(forward, w0: dict, x_train, y_train, n_train, *, loss, batch: int,
        steps: int, optimizer, seed: int, calls, client_block: int,
        nm, strategy: dict, server=None) -> dict:
    """``fedavg.run``'s contract; ``strategy["trainable"]`` names the path
    segments of the leaves that train."""
    if server is not None:
        raise ValueError("fedavg_adapters: the mean becomes the global "
                         "adapters; no server step")
    n_clients = len(n_train)
    if n_clients % client_block:
        raise ValueError("client_block must divide the number of clients")
    block = make_block_fn(forward, loss, optimizer[0], optimizer[1], nm)
    w_all = np.asarray(n_train, np.float32)
    total = float(w_all.sum())
    train0, frozen = split(w0, strategy["trainable"])
    train = nm.master(train0)
    one = optimizer[0].init(train, optimizer[1])
    opt_states = [jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (client_block, *a.shape)), one)
        for _ in range(0, n_clients, client_block)]
    losses, snaps = [], []
    with jax.default_matmul_precision("highest"):
        for n_rounds in calls:
            for r in range(1, n_rounds + 1):
                acc, loss_acc = None, 0.0
                for c0 in range(0, n_clients, client_block):
                    cs = range(c0, c0 + client_block)
                    idx = np.stack([_fedavg.index_plan(
                        seed, r, c, n_train[c], batch, steps) for c in cs])
                    rows = jnp.asarray(idx)
                    cid = jnp.arange(c0, c0 + client_block)[:, None, None]
                    xb, yb = x_train[cid, rows], y_train[cid, rows]
                    wb = jnp.asarray(w_all[c0:c0 + client_block])
                    wsum, lsum, opt_states[c0 // client_block] = block(
                        train, frozen, opt_states[c0 // client_block], xb, yb,
                        wb)
                    acc = wsum if acc is None else jax.tree_util.tree_map(
                        jnp.add, acc, wsum)
                    loss_acc = loss_acc + lsum
                train = nm.master(jax.tree_util.tree_map(
                    lambda a: a / total, acc))
                losses.append(float(loss_acc) / total)
            moved = _fedavg.leaf_norms(train, train0)
            snaps.append({**dict.fromkeys(frozen, 0.0), **moved})
    return {"losses": losses, "snapshots": snaps}
