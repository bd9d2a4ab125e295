"""What the decoder families over a frozen base share (``models/jamba.py``,
``models/deepseek.py``, ``models/nemotron_h.py``, ``models/afmoe.py``):
RMSNorm, the adapted projection, SwiGLU, the
declaration of a named parameter tree, the split into per-client adapters and
a base held once, and the form the base takes for a round (its matrices cast
to the compute type and written into one stack per run of layers).

Every adapted projection is ``W x + (alpha / r) * B^T (A^T x)`` as
``transformer.LoraDense`` has it. ``dims`` is a family's own dataclass of
sizes; the functions here read ``dims.dtype`` (the compute type at float32
parameters) and ``dims.lora_scale`` (alpha / rank, 0 without adapters).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from flax import linen as nn

from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.kernels.flash_attention import (SAVED_NAMES as FLASH_SAVED,
                                                   count_call_sites)
from fl4health_tpu.observability.stages import layer as part
from fl4health_tpu.utils.peft import per_client_predicate

# leaves every client holds: the adapters and the classification head
PER_CLIENT_MARKERS = ("lora_a", "lora_b", "score")
PER_CLIENT = per_client_predicate(PER_CLIENT_MARKERS)
F32 = jnp.float32

# What each family's rematerialised layer keeps (core/remat.py), spelled
# here once. Both keep the flash calls' ``out`` / ``lse``: per byte kept the
# dearest thing a layer would recompute. Latent attention also keeps its
# output stream ``h + o_proj(out)``: a frozen ``o_proj``'s backward reads
# none of its own product (the adapters' gradients read ``out`` and the
# ``[T, r]`` product ``out A``), so with the stream kept the recompute holds
# no product of ``o_proj``'s kernel at all. Jamba's mixers' stream and its
# scan's residuals are not kept: thirteen layers of them want memory that
# has to be freed first (ROADMAP S9).
MLA_STREAM = "mla_stream"
JAMBA_REMAT_KEEPS = FLASH_SAVED
DEEPSEEK_REMAT_KEEPS = (*FLASH_SAVED, MLA_STREAM)
# Nemotron-H's blocks are one mixer each: the attention block keeps the flash
# calls' pair; a Mamba-2 or an expert block keeps nothing (the chunked scan's
# chunk states are 4 MB a sequence and chunk, its decay tiles far more)
NEMOTRON_REMAT_KEEPS = FLASH_SAVED
# afmoe's layers (window and full attention alike) keep the flash calls' pair
AFMOE_REMAT_KEEPS = FLASH_SAVED


# ---------------------------------------------------------------------------
# The mathematics: pure functions over a dict of leaves
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    """x / rms(x) * scale, in float32 (the caller casts)."""
    with part("norm"):
        x = x.astype(F32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale


def lora_dense(p, x, dims):
    """x W + (alpha / r) * (x A) B in the compute type; no bias."""
    dt = dims.dtype
    x = x.astype(dt)
    y = x @ p["kernel"].astype(dt)
    if "lora_a" in p:
        with part("lora"):
            delta = dims.lora_scale * ((x @ p["lora_a"].astype(dt))
                                       @ p["lora_b"].astype(dt))
        y = y + delta
    return y


def swiglu(p, u, dims):
    gated = jax.nn.silu(lora_dense(p["gate_proj"], u, dims)) * lora_dense(
        p["up_proj"], u, dims)
    return lora_dense(p["down_proj"], gated, dims)


def dense_causal_attention(q, k, v, pad_mask, scale=None, window=None):
    """The plain form (float32 softmax) for when a family is given no
    ``attention_fn``: q / k [B, T, H, D], v [B, T, H, Dv], or k / v with one
    shared head; q and k may be tuples of parts whose scores add (D is their
    widths together, a part of k may have the one head alone); ``scale``
    None is ``1 / sqrt(D)``; under a ``window`` a query sees its own
    position and the ``window - 1`` before it."""
    qs = q if isinstance(q, (tuple, list)) else (q,)
    ks = k if isinstance(k, (tuple, list)) else (k,)
    t, d = qs[0].shape[1], sum(a.shape[-1] for a in qs)
    v = jnp.broadcast_to(v, (*qs[0].shape[:3], v.shape[-1]))
    scores = functools.reduce(jnp.add, (
        jnp.einsum("bqhd,bkhd->bhqk", a, jnp.broadcast_to(c, a.shape),
                   preferred_element_type=F32) for a, c in zip(qs, ks)))
    scores = (scores / jnp.sqrt(jnp.float32(d)) if scale is None
              else scores * scale)
    keep = (pad_mask[:, None, None, :] > 0) & (
        jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    if window is not None:
        keep = keep & (jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
                       < window)[None, None]
    attn = jax.nn.softmax(jnp.where(keep, scores, jnp.finfo(F32).min),
                          axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


def last_token_logits(h, pad_mask, final_scale, score_kernel, eps):
    """HF ``...ForSequenceClassification``'s head: the final-norm hidden
    state at the last non-pad token through ``score`` (no bias), float32."""
    h = rms_norm(h, final_scale, eps)
    with part("head"):
        last = jnp.maximum(pad_mask.sum(axis=1).astype(jnp.int32) - 1, 0)
        pooled = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        logits = pooled @ score_kernel.astype(F32)
        return {"prediction": logits.astype(F32)}, {"features": pooled}


def embed_tokens(embedding, x, dtype, scale=None):
    """The rows of ``embedding`` at the token ids ``x``, in ``dtype``;
    times ``scale`` (in the embedding's float32) where a family has one."""
    with part("embed"):
        rows = embedding[x]
        if scale is not None:
            rows = rows * scale
        return rows.astype(dtype)


def remat_layers(body, remat: bool, keeps):
    """``body`` rematerialised on the backward pass, less what ``keeps``
    names, if ``remat``."""
    return (jax.checkpoint(body, policy=remat_names.keep(keeps)) if remat
            else body)


# how a head of the newest causal flash call walks its live range
# (``kernels/flash_attention.py traversal``), under the gauges' own names
_WALK_GAUGES = {("flash_calls", key): f"flash_{key}"
                for key in ("tiles_edge", "tiles_interior", "cond_steps")}


def attention_gauges(module, batch_shape, n_clients: int, keeps,
                     **counters) -> dict:
    """Facts of ``module``'s build that follow from shapes at trace time:
    how many ``kernels.flash_attention`` calls one trace of its forward
    holds on each of the kernel's two paths (a run of layers under
    ``lax.scan`` traces its call once; likewise ``<name>_<path>`` for each
    further kernel's ``count_call_sites`` in ``counters``; a causal flash
    call's ``flash_tiles_edge`` / ``flash_tiles_interior`` /
    ``flash_cond_steps``, a head of the newest one), and what its
    remat sites keep of a layer (``core.remat.saved_gauges``; ``keeps`` is
    the family's list, zeros without ``module.remat``). Traced abstractly:
    nothing is allocated or run."""
    x = jax.ShapeDtypeStruct(tuple(batch_shape), jnp.int32)
    with contextlib.ExitStack() as stack:
        sites = {name: stack.enter_context(count())
                 for name, count in {"flash_calls": count_call_sites,
                                     **counters}.items()}
        variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    return {**{_WALK_GAUGES.get((name, path), f"{name}_{path}"): n
               for name, by_path in sites.items()
               for path, n in by_path.items()},
            **remat_names.saved_gauges(
                lambda v, x: module.apply(v, x)[0]["prediction"],
                (variables, x), keeps if module.remat else (), n_clients)}


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

INITS = {"matrix": nn.initializers.lecun_normal(),
         "zeros": nn.initializers.zeros, "ones": nn.initializers.ones,
         "embed": nn.initializers.normal(stddev=0.02)}


class Leaves(nn.Module):
    """Declares a nested dict of parameters from ``spec``, a tuple of
    ``(name, (shape, init))`` or ``(name, nested spec)`` pairs, and returns
    it: flax holds the names, the families' functions do the work. ``init``
    is a key of ``INITS`` or a family's own initializer."""

    spec: tuple

    @nn.compact
    def __call__(self):
        out = {}
        for name, entry in self.spec:
            if len(entry) == 2 and (isinstance(entry[1], str)
                                    or callable(entry[1])):
                shape, init = entry
                out[name] = self.param(name, INITS.get(init, init), shape)
            else:
                out[name] = Leaves(entry, name=name)()
        return out


def norm_spec(width: int) -> tuple:
    return (("scale", ((width,), "ones")),)


def proj_spec(name: str, n_in: int, n_out: int, rank: int) -> tuple:
    """A projection without bias; with ``rank`` its adapter beside it."""
    leaves = [("kernel", ((n_in, n_out), "matrix"))]
    if rank:
        # lora_b starts at zero: the adapted model starts at the base model
        leaves += [("lora_a", ((n_in, rank), "matrix")),
                   ("lora_b", ((rank, n_out), "zeros"))]
    return name, tuple(leaves)


# ---------------------------------------------------------------------------
# The base in the form a round consumes
# ---------------------------------------------------------------------------

def stack_by_writes(leaves):
    """``jnp.stack`` as writes of its own into one buffer. XLA:TPU splits a
    concatenate into one update per operand and gives the name stack to one
    of them only, so a trace could give ``fl_layer::shared_cast`` 14 of the
    base's 91 slices (PR 27); these updates all carry it. The price is the
    buffer's zeros: one more write of the stack (2.86 GB for Jamba's base,
    3.5 ms a round program on a v5e), behind a barrier or XLA folds it into a
    pad that has no name either."""
    out = jax.lax.optimization_barrier(
        jnp.zeros((len(leaves), *leaves[0].shape), leaves[0].dtype))
    for i, leaf in enumerate(leaves):
        out = jax.lax.dynamic_update_slice(
            out, leaf[None], (i,) + (0,) * leaf.ndim)
    return out


def _scan_member(tree, entry):
    """What one trip of a run's ``lax.scan`` consumes: layer ``entry``'s
    dict, or for a tuple of layer indices (a unit of unlike blocks that
    repeats) the dict of its members' dicts under their place in the unit."""
    if isinstance(entry, int):
        return tree.get(f"layers_{entry}")
    return {str(j): tree[f"layers_{i}"] for j, i in enumerate(entry)
            if f"layers_{i}" in tree} or None


def stack_runs(tree, runs, stack=jnp.stack):
    """The ``layers_<i>`` dicts of a tree (whole, or either half of the
    split) stacked over each of ``runs`` (lists of what one ``lax.scan``
    covers, a trip an entry: a layer index, or a tuple of them), under
    ``runs/<k>``."""
    out = {k: v for k, v in tree.items() if not k.startswith("layers_")}
    stacked = {}
    for k, run in enumerate(runs):
        members = [m for m in (_scan_member(tree, e) for e in run)
                   if m is not None]
        if members:
            stacked[str(k)] = jax.tree_util.tree_map(
                lambda *leaves: stack(leaves), *members)
    return {**out, "runs": stacked} if stacked else out


def prepare_shared(shared, runs, dtype, is_matrix):
    """The base as every client step of a round consumes it: the leaves that
    ``is_matrix(names)`` says are matmul operands in the compute type (norms,
    elementwise operands and the embedding's gather stay float32), the
    layers stacked over their runs, each cast writing its slice of the
    stack. ``names`` is the leaf's path as a list of keys."""
    def cast(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        return leaf.astype(dtype) if is_matrix(names) else leaf

    return stack_runs(jax.tree_util.tree_map_with_path(cast, shared), runs,
                      stack=stack_by_writes)
