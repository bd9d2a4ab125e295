"""Plain reference of the AFMoE (Arcee Trinity) sequence classifier: layers
whose attention is a sliding window or full by a published pattern, per-head
RMSNorm of q and k, a sigmoid gate on the attention output, four RMSNorms a
layer, leading dense SwiGLU layers and then sigmoid-routed SwiGLU experts
with one shared expert, LoRA adapters on the projections, and HF's
last-non-pad-token ``score`` head. float32, jax.numpy only, no kernels;
imports nothing of the program. Where the published ``config.json`` is silent
the line follows HF ``transformers``' ``modeling_afmoe.py`` ([modeling]; the
configuration file's ``assumed`` lists each).

h0 = E[x] * sqrt(hidden_size)                                  (mup_enabled)
Layer l, kind layer_types[l]:                          [modeling: sandwich]
  h <- h + post_attention_layernorm(Attn_l(input_layernorm(h)))
  h <- h + post_mlp_layernorm(FF_l(pre_mlp_layernorm(h)))
Attn_l(u): q = W_q u (heads x head_dim), k = W_k u, v = W_v u (kv_heads,
  each repeated over its heads/kv_heads consecutive query heads), g = W_g u
  [modeling: gate_proj]; q <- RMSNorm_head(q), k <- RMSNorm_head(k) with
  learned scales q_norm / k_norm [modeling]; on a sliding_attention layer
  rotary positions over the whole head, theta rope_theta, no scaling, the
  halves layout [x1 cos - x2 sin | x2 cos + x1 sin] (HF's rotate_half); on a
  full_attention layer NO positions [modeling]. Query i sees key j iff
  j <= i, j is no pad and, on a sliding layer, i - j < sliding_window;
  softmax(q k^T / sqrt(head_dim)) v, times sigmoid(g), through W_o; no bias.
FF_l, l < num_dense_layers: W_down (silu(W_gate u) * W_up u), intermediate_size.
FF_l otherwise: s = sigmoid(u W_r) over ALL router_width experts, float32;
  chosen = the num_experts_per_tok largest of s + expert_bias (the bias
  picks and does not weigh; n_group = topk_group = 1: no group limit);
  w_e = route_scale * s_e / (sum_chosen s + 1e-20)              (route_norm)
  out = sum_{e chosen and held here} w_e E_e(u) + E_shared(u), each expert a
  SwiGLU of moe_intermediate_size (the shared one num_shared_experts times it)
W x: every adapted projection is W x + (alpha / r) * B^T (A^T x)

Departures from the published model, each also under ``assumed`` in the
configuration file:
- the head: the final-RMSNorm hidden state at the last non-pad token through
  ``score`` (a class-label objective); no output head is built;
- the share: "held here" are the configuration's ``num_experts`` experts from
  ``first_expert_held`` of the router's ``router_width`` (a chip's share
  under expert parallelism). What the absent experts would add is left out,
  and the partial result goes on;
- a pad position (token id 0, at the tail) picks no expert: nothing reads it
  (every layer is causal), and a held expert among its picks would get every
  pad position of the batch as rows;
- weights are seeded (``param_spec``'s init kinds), not the published ones.

Attention at 8,192 positions: a [32, 8192, 8192] float32 score array is
8.6 GB, so the scores are computed for ``QUERY_BLOCK`` = 512 queries at a
time (all heads together: [32, 512, keys] is at most 0.5 GB), each block
against the keys it can see at all (a static slice: up to its last query,
and from ``sliding_window - 1`` before its first on a sliding layer), under
``jax.checkpoint`` so the backward pass holds one block's scores. The mask
inside a block is the plain one; the blocks change no value.

Contractions go through ``nm`` (reference/numerics.py) so that a control can
round their operands; the router (logits, sigmoid, choice, renormalisation),
the norms, the rotary tables, the gate's sigmoid and the softmax are float32
in every policy.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
QUERY_BLOCK = 512


def sizes(cfg: dict, job: dict) -> dict:
    n = cfg["num_hidden_layers"]
    return {
        "d": cfg["hidden_size"], "kinds": list(cfg["layer_types"][:n]),
        "dense": cfg["num_dense_layers"],
        "heads": cfg["num_attention_heads"],
        # a configuration cut to fewer query heads than it has key/value
        # heads (the CPU tests' toy twins) keeps a key/value head a query head
        "kv_heads": min(cfg["num_key_value_heads"],
                        cfg["num_attention_heads"]),
        "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"], "theta": float(cfg["rope_theta"]),
        "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "d_shared": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        "held": cfg["num_experts"], "first": cfg["first_expert_held"],
        "router": cfg["router_width"], "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["route_scale"]),
        "mup": bool(cfg["mup_enabled"]),
        "vocab": cfg["vocab_size"], "classes": cfg["num_labels"],
        "rank": cfg["lora_rank"],
        "scale": float(cfg["lora_alpha"]) / cfg["lora_rank"],
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_spec(s: dict, routed: bool) -> dict:
    """name inside a layer -> (shape, init)."""
    d, r, hd = s["d"], s["rank"], s["head_dim"]
    spec = {f"{name}/scale": ((d,), "ones") for name in (
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm")}

    def proj(name, n_in, n_out, adapted=True):
        spec[f"{name}/kernel"] = ((n_in, n_out), "fan_in")
        if adapted:
            spec[f"{name}/lora_a"] = ((n_in, r), "fan_in")
            # N(0, 0.02^2) and not zeros: every adapter leaf has a
            # first-order gradient from the first round
            spec[f"{name}/lora_b"] = ((r, n_out), "embed")

    def mlp(name, width, adapted=True):
        proj(f"{name}/gate_proj", d, width, adapted)
        proj(f"{name}/up_proj", d, width, adapted)
        proj(f"{name}/down_proj", width, d, adapted)

    proj("self_attn/q_proj", d, s["heads"] * hd)
    proj("self_attn/k_proj", d, s["kv_heads"] * hd)
    proj("self_attn/v_proj", d, s["kv_heads"] * hd)
    proj("self_attn/gate_proj", d, s["heads"] * hd)
    proj("self_attn/o_proj", s["heads"] * hd, d)
    spec["self_attn/q_norm/scale"] = ((hd,), "ones")
    spec["self_attn/k_norm/scale"] = ((hd,), "ones")
    if routed:
        proj("mlp/router", d, s["router"], adapted=False)
        # drawn N(0, 0.02^2) and not zeros, so that what picks an expert and
        # what weighs it differ in every run
        spec["mlp/expert_bias"] = ((s["router"],), "embed")
        # one leaf per expert and matrix: the generator scales a matrix by
        # all axes but the last, so a stack over experts would be seeded
        # sqrt(held) too small
        for j in range(s["held"]):
            mlp(f"mlp/experts_{j}", s["d_expert"], adapted=False)
        mlp("mlp/shared_experts", s["d_shared"])
    else:
        mlp("mlp", s["d_ff"])
    return spec


def param_spec(cfg: dict, job: dict) -> dict:
    """path -> (shape, init). Paths are '/'-joined names."""
    s = sizes(cfg, job)
    spec = {"embed_tokens/embedding": ((s["vocab"], s["d"]), "embed"),
            "norm/scale": ((s["d"],), "ones"),
            "score/kernel": ((s["d"], s["classes"]), "fan_in")}
    for i in range(len(s["kinds"])):
        for name, entry in layer_spec(s, i >= s["dense"]).items():
            spec[f"layers_{i}/{name}"] = entry
    return spec


def input_spec(cfg: dict, job: dict) -> dict:
    return {"kind": "tokens", "vocab": cfg["vocab_size"],
            "seq": job["data"]["seq"], "classes": cfg["num_labels"],
            "min_len_frac": job["data"].get("min_len_frac", 1.0)}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _proj(p, name, x, s, nm):
    y = nm.dot(x, p[f"{name}/kernel"])
    if f"{name}/lora_a" in p:
        y = y + s["scale"] * nm.dot(nm.dot(x, p[f"{name}/lora_a"]),
                                    p[f"{name}/lora_b"])
    return y


def _swiglu(p, name, x, s, nm):
    return _proj(p, f"{name}/down_proj", jax.nn.silu(
        _proj(p, f"{name}/gate_proj", x, s, nm)) * _proj(
        p, f"{name}/up_proj", x, s, nm), s, nm)


def _rotary(x, theta):
    """x [B, T, H, dim] -> [x1 cos - x2 sin | x2 cos + x1 sin] with x1 | x2
    the two halves and angle t * theta^(-2i / dim) at position t, lane i."""
    t, dim = x.shape[1], x.shape[-1]
    inv = jnp.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)],
                      jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, pad_mask, window, nm):
    """q / k / v [B, T, H, D] (k, v already repeated to the query heads) ->
    [B, T, H, D]: ``QUERY_BLOCK`` queries at a time against the keys they
    can see at all, the plain mask inside (module docstring)."""
    t, d = q.shape[1], q.shape[-1]

    @jax.checkpoint
    def block(qb, kb, vb, maskb, q0, k0):
        i = q0 + jnp.arange(qb.shape[1])[:, None]
        j = k0 + jnp.arange(kb.shape[1])[None, :]
        keep = (j <= i)
        if window is not None:
            keep = keep & (i - j < window)
        keep = keep[None, None] & (maskb[:, None, None, :] > 0)
        scores = nm.einsum("bqhd,bkhd->bhqk", qb, kb) / jnp.sqrt(
            jnp.float32(d))
        attn = jax.nn.softmax(
            jnp.where(keep, scores, jnp.finfo(jnp.float32).min), axis=-1)
        return nm.einsum("bhqk,bkhd->bqhd", attn, vb)

    outs = []
    for q0 in range(0, t, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, t)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        outs.append(block(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1],
                          pad_mask[:, k0:q1], q0, k0))
    return jnp.concatenate(outs, axis=1)


def _attention(p, u, pad_mask, kind, s, nm):
    bsz, t, _ = u.shape
    hd, groups = s["head_dim"], s["heads"] // s["kv_heads"]
    q = _proj(p, "self_attn/q_proj", u, s, nm).reshape(bsz, t, s["heads"], hd)
    k = _proj(p, "self_attn/k_proj", u, s, nm).reshape(
        bsz, t, s["kv_heads"], hd)
    v = _proj(p, "self_attn/v_proj", u, s, nm).reshape(
        bsz, t, s["kv_heads"], hd)
    gate = _proj(p, "self_attn/gate_proj", u, s, nm)
    q = _rms_norm(q, p["self_attn/q_norm/scale"], s["eps"])
    k = _rms_norm(k, p["self_attn/k_norm/scale"], s["eps"])
    sliding = kind == SLIDING
    if sliding:
        q, k = _rotary(q, s["theta"]), _rotary(k, s["theta"])
    # query head h reads key/value head h // groups
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    out = _attend(q, k, v, pad_mask, s["window"] if sliding else None, nm)
    out = out.reshape(bsz, t, s["heads"] * hd) * jax.nn.sigmoid(gate)
    return _proj(p, "self_attn/o_proj", out, s, nm)


def route(p, u, s):
    """u [N, d] -> combine weights [N, router]: route_scale * s_e /
    sum_chosen s for the chosen experts, 0 elsewhere. float32 in every
    policy."""
    scores = jax.nn.sigmoid(jnp.matmul(
        u, p["mlp/router/kernel"], precision=jax.lax.Precision.HIGHEST))
    picking = scores + p["mlp/expert_bias"]
    # the top_k-th largest of what picks is the bar an expert must reach
    bar = jnp.sort(picking, axis=-1)[:, -s["top_k"]][:, None]
    chosen = jnp.where(picking >= bar, scores, 0.0)
    return s["route_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _moe(p, u, pad_mask, s, nm):
    flat = u.reshape(-1, u.shape[-1])
    # a pad position picks no expert
    combine = route(p, flat, s) * pad_mask.reshape(-1, 1)
    y = jnp.zeros_like(flat)
    # every held expert over every token, weighted by what the router gave
    # it (0 for a token that did not choose it): plain, and nothing like the
    # program's sorted tiles
    for j in range(s["held"]):
        y = y + combine[:, s["first"] + j, None] * _swiglu(
            p, f"mlp/experts_{j}", flat, s, nm)
    return y.reshape(u.shape) + _swiglu(p, "mlp/shared_experts", u, s, nm)


def _layer(p, h, pad_mask, kind, routed, s, nm):
    eps = s["eps"]
    u = _rms_norm(h, p["input_layernorm/scale"], eps)
    h = h + _rms_norm(_attention(p, u, pad_mask, kind, s, nm),
                      p["post_attention_layernorm/scale"], eps)
    u = _rms_norm(h, p["pre_mlp_layernorm/scale"], eps)
    ff = (_moe(p, u, pad_mask, s, nm) if routed
          else _swiglu(p, "mlp", u, s, nm))
    return h + _rms_norm(ff, p["post_mlp_layernorm/scale"], eps)


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """params: flat path -> float32 array. x: int tokens [B, T]. Returns
    float32 logits [B, classes]. One layer after another, each
    rematerialised on the backward pass (no value changes)."""
    s = sizes(cfg, job)
    pad_mask = (x > 0).astype(jnp.float32)
    h = params["embed_tokens/embedding"][x]
    if s["mup"]:
        h = h * math.sqrt(s["d"])
    for i, kind in enumerate(s["kinds"]):
        prefix = f"layers_{i}/"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        h = jax.checkpoint(
            lambda p_, h_, kind=kind, routed=i >= s["dense"]: _layer(
                p_, h_, pad_mask, kind, routed, s, nm))(p, h)
    h = _rms_norm(h, params["norm/scale"], s["eps"])
    last = jnp.maximum(pad_mask.sum(axis=1).astype(jnp.int32) - 1, 0)
    pooled = h[jnp.arange(x.shape[0]), last]
    return nm.dot(pooled, params["score/kernel"])
