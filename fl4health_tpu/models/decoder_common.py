"""What the decoder families over a frozen base share: RMSNorm, the adapted
projection, SwiGLU, rotary positions, the causal depthwise conv, the
declaration of a named parameter tree, and the stack itself (``DecoderStack``:
the leaves, the forward over runs of layers, the split into per-client
adapters and a base held once, and the form the base takes for a round: its
matrices cast to the compute type and written into one stack per run of
layers). A family declares its blocks and inherits the rest; the routed-expert
layer three of them share is ``models/routed.py``.

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
from fl4health_tpu.core.pytree import merge_trees
from fl4health_tpu.kernels.flash_attention import (SAVED_NAMES as FLASH_SAVED,
                                                   count_call_sites)
from fl4health_tpu.observability.stages import layer as part
from fl4health_tpu.utils.peft import per_client_predicate

# leaves every client holds: the adapters and the classification head
PER_CLIENT_MARKERS = ("lora_a", "lora_b", "score")
PER_CLIENT = per_client_predicate(PER_CLIENT_MARKERS)
F32 = jnp.float32

# the name of latent attention's output stream ``h + o_proj(out)`` for a remat
# site to keep (``models/deepseek.py REMAT_KEEPS`` has the reason)
MLA_STREAM = "mla_stream"


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


def causal_depthwise_conv(p, x):
    """y_t = sum_j kernel[j] * x_{t - (K - 1) + j} + bias per channel (HF
    ``conv1d.weight[c, 0, j]`` is ``kernel[j, c]``), float32."""
    width, t = p["kernel"].shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * p["kernel"][j] for j in range(width)) + p["bias"]


def rope_tables(t: int, dim: int, theta: float, inv_freq=None, scale=1.0):
    """(cos, sin) [T, dim // 2], float32: plain rotary embedding at ``theta``,
    or over a scaling method's own ``dim // 2`` inverse frequencies, times
    its cos/sin ``scale`` (``deepseek.rope_tables``: YaRN's)."""
    if inv_freq is None:
        inv_freq = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    inv = jnp.asarray(inv_freq, F32)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x [B, T, H, dim] in the halves layout ``[x1 | x2]`` -> ``[x1 cos - x2
    sin | x2 cos + x1 sin]``, computed in float32. (HF permutes each
    interleaved pair to this layout first; with seeded weights that is a
    relabelling of the columns of the projections that write ``x``.)"""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


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


def swiglu_spec(d: int, width: int, rank: int) -> tuple:
    """The three projections ``swiglu`` reads."""
    return (proj_spec("gate_proj", d, width, rank),
            proj_spec("up_proj", d, width, rank),
            proj_spec("down_proj", width, d, rank))


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


def pattern_runs(kinds, max_unit: int = 1) -> list[list]:
    """The layers, given by their ``kinds`` in order, cut into runs that one
    ``lax.scan`` each covers: a run is a unit of one up to ``max_unit``
    layers and its immediate repeats, a trip a unit. Greedy from the left,
    the unit that covers most. At ``max_unit`` 1 a run is layers alike in
    kind that follow one another and a trip is a layer index (Jamba's
    ``[[0..6], [7], [8..13]]``); above it a trip is a tuple of indices, a
    lone layer's too (``MEMEMEM*EME`` at 2 -> ``[(0, 1), (2, 3), (4, 5)]``,
    ``[(6,)]``, ``[(7,)]``, ``[(8,)]``, ``[(9,)]``, ``[(10,)]``):
    ``_scan_member`` takes both, and the stacked tree's paths hang on which."""
    kinds = tuple(kinds)
    runs, i = [], 0
    while i < len(kinds):
        best = (1, 1)
        for size in range(1, max_unit + 1):
            unit, reps = kinds[i:i + size], 1
            while kinds[i + reps * size:i + (reps + 1) * size] == unit:
                reps += 1
            # a longer unit has to repeat to be worth a body of its own
            if len(unit) == size and (size == 1 or reps > 1) and (
                    size * reps > best[0] * best[1]):
                best = (size, reps)
        size, reps = best
        units = [tuple(range(i + k * size, i + (k + 1) * size))
                 for k in range(reps)]
        runs.append(units if max_unit > 1 else [unit[0] for unit in units])
        i += size * reps
    return runs


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

class DecoderStack(nn.Module):
    """A decoder over a frozen base with a last-token classification head
    (HF ``...ForSequenceClassification``). Input: integer token ids [B, T],
    id 0 = padding at the tail. A family is a subclass that declares its
    fields (the stack reads ``vocab_size``, ``n_classes``, ``d_model``,
    ``rms_eps``, ``dtype``, ``remat``), ``dims`` (the dataclass of sizes its
    block functions read), and

    - ``kinds()``: the kind of each layer, in order (values that compare);
    - ``spec(kind)``: the ``Leaves`` spec of a layer of that kind;
    - ``block(p, h, pad_mask, kind, dims)``, a staticmethod (flax then leaves
      it unwrapped: no scope of its own in an op's name): one layer of that
      kind over its dict ``p``, the stream in and out;
    - the attributes below, where it differs from them.

    The stack owns the rest: the leaves (``embed_tokens``, the final norm,
    ``score``, ``layers_<i>``), the forward (consecutive layers, or units of
    unlike layers, that repeat run as ONE ``lax.scan`` over their stacked
    dicts, each layer rematerialised on the backward pass under ``remat``),
    the split of the parameters (``per_client_param``: adapters and head per
    client, the base shared) and the forward over the two halves
    (``bind_shared``), which ``clients/engine.from_flax`` hands to the
    engine: the base then exists once on the device however many clients
    train adapters over it. ``build_gauges`` is a family's own."""

    max_unit = 1  # the longest unit of unlike layers ``runs()`` looks for
    final_norm = "norm"  # the final norm's leaf, as the checkpoint names it
    embed_scale = None  # a factor on the embedding's rows
    remat_keeps = FLASH_SAVED  # what a rematerialised layer keeps
    float32_kernels = ()  # parents whose ``kernel`` is no matmul operand

    def runs(self) -> list[list]:
        return pattern_runs(self.kinds(), self.max_unit)

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # no dropout, no batch statistics
        d = self.d_model
        spec = [("embed_tokens", (("embedding", ((self.vocab_size, d),
                                                 "embed")),)),
                (self.final_norm, norm_spec(d)),
                ("score", (("kernel", ((d, self.n_classes), "matrix")),))]
        spec += [(f"layers_{i}", self.spec(kind))
                 for i, kind in enumerate(self.kinds())]
        params = {name: Leaves(entry, name=name)() for name, entry in spec}
        return self.forward(stack_runs(params, self.runs()), x)

    def forward(self, stacked, x):
        """``stacked``: the tree with its layers stacked by ``stack_runs``
        over ``runs()``. Each run is one ``lax.scan`` over its stack, a trip
        one unit. (One scan over all of Jamba's Mamba layers with the
        attention layer under a ``lax.cond`` would compile one body fewer,
        but XLA then plans 13.1 GB of temporaries for the round where this
        form takes 9.9: PR 27.)"""
        dims, kinds = self.dims, self.kinds()
        pad_mask = (x > 0).astype(F32)
        h = embed_tokens(stacked["embed_tokens"]["embedding"], x, self.dtype,
                         scale=self.embed_scale)
        for k, run in enumerate(self.runs()):
            lone = isinstance(run[0], int)
            unit = [kinds[i] for i in ((run[0],) if lone else run[0])]

            def body(h_, p, unit=unit, lone=lone):
                for j, kind in enumerate(unit):
                    # one remat site a layer: a site for each half of it
                    # (attention, feed-forward) made XLA's plan for the
                    # window cell's round program LARGER (14.31 GiB of
                    # temporaries for 12.75: one more copy of the stream
                    # kept a layer, and no array recomputed later)
                    one = remat_layers(
                        lambda h__, q, kind=kind: self.block(
                            q, h__, pad_mask, kind, dims).astype(self.dtype),
                        self.remat, self.remat_keeps)
                    h_ = one(h_, p if lone else p[str(j)])
                return h_, None

            h, _ = jax.lax.scan(body, h, stacked["runs"][str(k)])
        return last_token_logits(
            h, pad_mask, stacked[self.final_norm]["scale"],
            stacked["score"]["kernel"], self.rms_eps)

    # -- the split of the parameters (clients/engine.py ModelDef) ----------
    def per_client_param(self, path: str) -> bool:
        return PER_CLIENT(path)

    def prepare_shared(self, shared):
        """The base as every client step of a round consumes it: each
        projection's and expert's ``kernel`` in the compute type (those under
        a name in ``float32_kernels``, the norms, every elementwise operand
        and the embedding's gather stay float32), the layers stacked over
        their runs, each cast writing its slice of the stack."""
        def cast(path, leaf):
            names = [getattr(k, "key", None) for k in path]
            matrix = (names[-1] == "kernel"
                      and names[-2] not in self.float32_kernels)
            return leaf.astype(self.dtype) if matrix else leaf

        return stack_runs(jax.tree_util.tree_map_with_path(cast, shared),
                          self.runs(), stack=stack_by_writes)

    def bind_shared(self, shared):
        """``(per_client, x) -> (preds, features)`` over a base prepared
        here, once a round, under the ``fl_layer::shared_cast`` scope: the
        client's own leaves are stacked at each call (they are small)."""
        with part("shared_cast"):
            prepared = self.prepare_shared(shared)
        return lambda per_client, x: self.forward(
            merge_trees(prepared, stack_runs(per_client, self.runs())), x)
