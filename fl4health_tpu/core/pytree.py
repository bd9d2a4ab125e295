"""Pytree manipulation primitives underlying exchange, packing, and DP.

These replace the reference's NumPy list-of-arrays plumbing
(/root/reference/fl4health/parameter_exchange/parameter_packer.py) with
jit-compatible pytree transforms:

- flat-vector round trips (for clipping, drift norms, packing),
- leaf selection by path predicate (layer exchangers),
- client-axis stack/unstack (the SPMD "wire"),
- linear-algebra helpers (global norm, weighted sums) used everywhere.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.flatten_util
import jax.numpy as jnp

from fl4health_tpu.core.types import PyTree, tree_zeros_like  # noqa: F401  (re-export)


# ---------------------------------------------------------------------------
# Path naming
# ---------------------------------------------------------------------------

def leaf_paths(tree: PyTree) -> list[str]:
    """Dotted string path for every leaf, in tree order.

    Plays the role of torch ``state_dict`` keys for layer-wise exchange
    (reference: parameter_exchange/layer_exchanger.py:17 keys on state_dict).
    """
    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [_path_str(path) for path, _ in paths_leaves]


def _path_str(path: tuple) -> str:
    parts = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            parts.append(str(entry.key))
        elif isinstance(entry, jax.tree_util.SequenceKey):
            parts.append(str(entry.idx))
        elif isinstance(entry, jax.tree_util.GetAttrKey):
            parts.append(str(entry.name))
        else:  # FlattenedIndexKey and friends
            parts.append(str(getattr(entry, "key", entry)))
    return ".".join(parts)


def select_by_path(tree: PyTree, predicate: Callable[[str], bool]) -> PyTree:
    """Return a mask tree: True where the leaf's dotted path satisfies predicate."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    mask = [bool(predicate(_path_str(p))) for p, _ in paths_leaves]
    return jax.tree_util.tree_unflatten(treedef, mask)


def merge_by_mask(mask: PyTree, if_true: PyTree, if_false: PyTree) -> PyTree:
    """Leafwise select between two trees by a boolean mask tree."""
    return jax.tree_util.tree_map(
        lambda m, t, f: t if m else f, mask, if_true, if_false
    )


def split_by_path(
    tree: PyTree, predicate: Callable[[str], bool], prefix: str = ""
) -> tuple[PyTree, PyTree]:
    """Split a nested-mapping tree (flax params) into (leaves whose dotted
    path satisfies ``predicate``, the others), each a nested dict that holds
    only its own leaves; branches left empty are dropped. ``merge_trees``
    is the inverse."""
    yes, no = {}, {}
    for key, value in tree.items():
        path = f"{prefix}.{key}" if prefix else str(key)
        if hasattr(value, "items"):
            sub_yes, sub_no = split_by_path(value, predicate, path)
            if sub_yes:
                yes[key] = sub_yes
            if sub_no:
                no[key] = sub_no
        elif predicate(path):
            yes[key] = value
        else:
            no[key] = value
    return yes, no


def merge_trees(a: PyTree, b: PyTree) -> PyTree:
    """Union of two nested-mapping trees with disjoint leaves (the two
    halves ``split_by_path`` returned)."""
    out = dict(a)
    for key, value in b.items():
        if key in out:
            if not (hasattr(value, "items") and hasattr(out[key], "items")):
                raise ValueError(f"merge_trees: both trees hold a leaf at {key!r}")
            out[key] = merge_trees(out[key], value)
        else:
            out[key] = value
    return out


# ---------------------------------------------------------------------------
# Flat-vector round trips
# ---------------------------------------------------------------------------

def ravel(tree: PyTree) -> tuple[jax.Array, Callable[[jax.Array], PyTree]]:
    """Flatten a pytree to one 1-D vector; returns (vector, unravel_fn)."""
    flat, unravel = jax.flatten_util.ravel_pytree(tree)
    return flat, unravel


def global_norm(tree: PyTree) -> jax.Array:
    """l2 norm over all leaves (reference: losses/weight_drift_loss.py:5 uses
    per-tensor linalg.norm summed; we define the global norm and also expose
    per-leaf norms below)."""
    sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree))
    return jnp.sqrt(sq)


def leaf_norms(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x: jnp.linalg.norm(x.reshape(-1)), tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, c) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * c, tree)


def tree_axpy(a, x: PyTree, y: PyTree) -> PyTree:
    """a * x + y, leafwise."""
    return jax.tree_util.tree_map(lambda xi, yi: a * xi + yi, x, y)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    return sum(
        jnp.vdot(x, y)
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# ---------------------------------------------------------------------------
# Client-axis stacking — the SPMD "wire format"
# ---------------------------------------------------------------------------

def stack_clients(trees: Sequence[PyTree]) -> PyTree:
    """Stack per-client pytrees along a new leading clients axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_clients(stacked: PyTree, n: int) -> list[PyTree]:
    return [jax.tree_util.tree_map(lambda x, i=i: x[i], stacked) for i in range(n)]


def client_slice(stacked: PyTree, i) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def broadcast_clients(tree: PyTree, n: int) -> PyTree:
    """Replicate a tree n times along a new leading clients axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree
    )


# ---------------------------------------------------------------------------
# Casting helpers
# ---------------------------------------------------------------------------

def tree_astype(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


# ---------------------------------------------------------------------------
# Size accounting
# ---------------------------------------------------------------------------

def tree_nbytes(tree: PyTree) -> int:
    """Total byte footprint of a pytree's array leaves, from shape/dtype
    metadata only (works on concrete arrays AND ``jax.eval_shape`` structs;
    no device transfer). The ONE definition the observability byte
    accounting uses — payload wire-cost (server/simulation.py) and staged
    data stacks (clients/engine.py) must agree on what a byte is."""
    import numpy as np

    return int(sum(
        int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    ))
