"""The one general generator: weights and client data from ``--seed``, made
on the device in one jitted call each. A traffic mix is a data file of
parameters that this module reads; it holds no code of its own."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed31(seed: int) -> int:
    """Any whole-number seed (the driver's exceed 32 signed bits) folded to a
    non-negative 31-bit integer that every consumer can hold."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def make_weights(spec: dict, seed: int) -> dict:
    """path -> float32 array, per the reference's ``param_spec``: matrices
    N(0, 1/fan_in) (fan_in = product of all but the last axis), embeddings
    N(0, 0.02^2), biases 0, norm scales 1."""
    paths = sorted(spec)

    @jax.jit
    def gen(key):
        out = {}
        for i, path in enumerate(paths):
            shape, init = spec[path]
            if init == "zeros":
                out[path] = jnp.zeros(shape, jnp.float32)
            elif init == "ones":
                out[path] = jnp.ones(shape, jnp.float32)
            else:
                std = (0.02 if init == "embed"
                       else 1.0 / math.sqrt(math.prod(shape[:-1])))
                out[path] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return gen(jax.random.PRNGKey(seed31(seed)))


def client_rows(job: dict) -> list[int]:
    """Training rows of each client: the traffic file's list, cycled."""
    sizes = job["train_examples"]
    if isinstance(sizes, int):
        sizes = [sizes]
    rows = [int(sizes[i % len(sizes)]) for i in range(int(job["clients"]))]
    for n in rows:
        if n % int(job["batch"]):
            raise ValueError(f"train_examples {n} is not a multiple of the "
                             f"batch {job['batch']}")
    return rows


def make_data(inp: dict, job: dict, seed: int, targets, objective: dict):
    """(x_train [C, n_max, ...], y_train [C, n_max, ...], x_val, y_val) on
    the device. Rows beyond a client's own count exist in the stacked array
    and are never indexed. ``inp`` is the reference's ``input_spec``;
    ``targets`` is the plain side of the job's objective
    (``reference/objectives/<name>.py``), ``objective`` the traffic file's
    entry: what the clients learn is drawn here, with the data, so the
    program and the reference start from the same targets."""
    n_clients, n_val = int(job["clients"]), int(job["val_examples"])
    n_max = max(client_rows(job))
    n = n_max + n_val

    @jax.jit
    def gen(key):
        k_x, _, k_len, _ = jax.random.split(key, 4)
        length = None
        if inp["kind"] == "tokens":
            seq, vocab = int(inp["seq"]), int(inp["vocab"])
            tok = jax.random.randint(k_x, (n_clients, n, seq), 1, vocab,
                                     jnp.int32)
            lo = max(1, int(seq * float(inp.get("min_len_frac", 1.0))))
            length = jax.random.randint(k_len, (n_clients, n, 1), lo, seq + 1)
            x = jnp.where(jnp.arange(seq)[None, None, :] < length, tok, 0)
        elif inp["kind"] == "images":
            hw, ch = int(inp["hw"]), int(inp["channels"])
            x = jax.random.normal(k_x, (n_clients, n, hw, hw, ch), jnp.float32)
        else:
            raise ValueError(f"unknown data kind {inp['kind']!r}")
        # the targets' own key: a fold beside the split above (kept four
        # wide, though two of its keys are no longer drawn from), so an
        # objective that draws nothing leaves every bit where it was
        x, y = targets(jax.random.fold_in(key, 4), x, length, inp, objective)
        return x[:, :n_max], y[:, :n_max], x[:, n_max:], y[:, n_max:]

    return gen(jax.random.fold_in(jax.random.PRNGKey(seed31(seed)), 7))
