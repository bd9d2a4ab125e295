"""Plain FedAvg rounds over a reference model: the semantics a cell's timed
``fit()`` calls are held to.

One round: every client starts from the global weights, takes
``local_steps`` steps of the job's optimizer (a plain module found by name
under ``reference/optimizers/``, its state kept by the client from round to
round as a client keeps its optimizer) on batches
of ``batch`` rows drawn by the loader's shuffle, reports the mean of its
step losses (each taken before that step's update); the server replaces the
global weights by the mean of the clients' weights weighted by their
training-set sizes, and the round's loss is the same weighted mean of the
clients' losses. A strategy that does something else with that mean passes
``server=(init, update)`` to ``run`` from a file of its own beside this one.

The shuffle is the loader's documented rule, rebuilt here from the job's
seed with numpy alone: client ``i`` in round ``r`` permutes its rows with
``default_rng(SeedSequence([0, seed, 1000 + r, i]))`` (one permutation per
epoch, epochs concatenated, cut into batches, first ``local_steps`` kept).
Every ``fit()`` call numbers its rounds from 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def index_plan(seed: int, round_idx: int, client: int, n: int, batch: int,
               steps: int) -> np.ndarray:
    """[steps, batch] row indices of one client's round."""
    if n % batch:
        raise ValueError(f"client rows {n} not a multiple of batch {batch}")
    per_epoch = n // batch
    n_epochs = -(-steps // per_epoch)
    rng = np.random.default_rng(
        np.random.SeedSequence([0, int(seed), 1000 + int(round_idx), int(client)]))
    orders = rng.permuted(
        np.tile(np.arange(n, dtype=np.int32), (n_epochs, 1)), axis=1)
    return orders.reshape(n_epochs * per_epoch, batch)[:steps]


def make_block_fn(forward, loss, opt_mod, opt: dict, nm):
    """jitted (params, states [Cb,...], xb [Cb,S,B,...], yb [Cb,S,B,...],
    w [Cb]) -> (sum_i w_i * params_i, sum_i w_i * loss_i, new states)."""

    def client(params, state, xb, yb):
        def step(carry, batch):
            p, state = carry
            x, y = batch
            value, g = jax.value_and_grad(
                lambda q: loss(forward(q, x, nm), x, y, nm))(p)
            p, state = opt_mod.update(p, g, state, opt)
            return (nm.master(p), state), value
        (p, state), losses = jax.lax.scan(step, (params, state), (xb, yb))
        return p, state, jnp.mean(losses)

    @jax.jit
    def block(params, states, xb, yb, w):
        ps, states, losses = jax.vmap(client, in_axes=(None, 0, 0, 0))(
            params, states, xb, yb)
        wsum = jax.tree_util.tree_map(
            lambda a: jnp.tensordot(w, a, axes=1), ps)
        return wsum, jnp.sum(w * losses), states

    return block


def leaf_norms(params: dict, base: dict) -> dict:
    """path -> ||params - base|| per leaf (float)."""
    out = jax.jit(lambda p, b: {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - b[k])))
                                for k in p})(params, base)
    return {k: float(v) for k, v in out.items()}


def run(forward, w0: dict, x_train, y_train, n_train, *, loss, batch: int,
        steps: int, optimizer, seed: int, calls, client_block: int,
        nm, strategy: dict | None = None, server=None) -> dict:
    """Follow ``calls`` (a list of rounds-per-fit, e.g. [1, 2]) from ``w0``.

    ``x_train`` [C, n_max, ...] and ``y_train`` [C, n_max, ...] are device
    arrays, ``n_train`` the per-client row counts, ``loss(out, x, y, nm)`` the
    step's loss from ``forward``'s output (the plain side of the job's
    objective, ``reference/objectives/``). Returns per-round losses (in
    order) and, after each call, the per-leaf norms of the global weights'
    change from ``w0``. Clients run ``client_block`` at a time so the
    reference fits beside nothing else on the device. ``nm`` is the
    precision policy (``numerics.FLOAT32`` for the reference proper, one of
    ``numerics.CONTROLS`` for a control), ``optimizer`` the pair (plain
    optimizer module, its parameters from the traffic file), ``strategy``
    the traffic file's strategy entry (FedAvg has no parameters), ``server``
    an optional (init(params) -> state, update(params, mean, state) ->
    (params, state)) in place of "the mean becomes the global weights"."""
    n_clients = len(n_train)
    if n_clients % client_block:
        raise ValueError("client_block must divide the number of clients")
    block = make_block_fn(forward, loss, optimizer[0], optimizer[1], nm)
    w_all = np.asarray(n_train, np.float32)
    total = float(w_all.sum())
    params = nm.master(w0)
    server_state = server[0](params) if server else None
    # every client's optimizer state, one stacked tree per block of clients
    one = optimizer[0].init(params, optimizer[1])
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
                    idx = np.stack([index_plan(seed, r, c, n_train[c], batch,
                                               steps) for c in cs])
                    rows = jnp.asarray(idx)
                    cid = jnp.arange(c0, c0 + client_block)[:, None, None]
                    xb, yb = x_train[cid, rows], y_train[cid, rows]
                    wb = jnp.asarray(w_all[c0:c0 + client_block])
                    wsum, lsum, opt_states[c0 // client_block] = block(
                        params, opt_states[c0 // client_block], xb, yb, wb)
                    acc = wsum if acc is None else jax.tree_util.tree_map(
                        jnp.add, acc, wsum)
                    loss_acc = loss_acc + lsum
                mean = jax.tree_util.tree_map(lambda a: a / total, acc)
                if server:
                    mean, server_state = server[1](params, mean, server_state)
                params = nm.master(mean)
                losses.append(float(loss_acc) / total)
            snaps.append(leaf_norms(params, w0))
    return {"losses": losses, "snapshots": snaps}
