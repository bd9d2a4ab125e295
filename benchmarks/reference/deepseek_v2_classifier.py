"""Plain reference of the DeepSeek-V2 sequence classifier: multi-head latent
attention (MLA) with YaRN rotary positions, a leading dense SwiGLU layer,
then layers of routed experts (``group_limited_greedy``) beside shared
experts, RMSNorm, LoRA adapters on the projections, and HF
``DeepseekV2ForSequenceClassification``'s head. float32, jax.numpy only, no
kernels; imports nothing of the program.

Layer l, for the stream h [B, T, d]: u = RMSNorm(h);

  c_q = RMSNorm(u W_qa); [q_nope | q_pe] = c_q W_qb per head (nope | rope)
  [c_kv | k_pe] = u W_kva; c_kv <- RMSNorm(c_kv)
  [k_nope | v] = c_kv W_kvb per head (nope | v_head)
  q_pe, k_pe <- RoPE_t(.), YaRN inverse frequencies; k_pe is ONE head's,
       shared by all; the HALVES layout [x1 | x2] -> [x1 cos - x2 sin |
       x2 cos + x1 sin] (HF permutes interleaved pairs into this layout
       first: with seeded weights a relabelling of columns)
  q = [q_nope | q_pe], k = [k_nope | k_pe]
  a = softmax(q k^T * (nope + rope)^-0.5 * m^2) under the causal and
       key-padding masks, m = 0.1 * mscale_all_dim * ln(factor) + 1
  h <- h + (a v) W_o

then u = RMSNorm(h) and

  l < first_k_dense_replace:  h <- h + SwiGLU_intermediate(u)
  else: s = softmax(u W_g) over ALL the router's experts, float32
        group score = max of s over each of n_group groups; the topk_group
        best groups keep their scores, the others read 0; the
        num_experts_per_tok largest of what is left are chosen,
        w_i = routed_scaling_factor * s_i (not renormalised)
        h <- h + sum_{i chosen and held here} w_i E_i(u) + SwiGLU_shared(u)
        E_i(u) = W_down,i (silu(W_gate,i u) * W_up,i u)

"Held here": the configuration's ``n_routed_experts`` experts from
``first_expert_held`` of the router's ``router_width`` (a chip's share under
expert parallelism). What the absent experts would add is left out, and the
partial result goes on. ``W x`` of an adapted projection is
``W x + (alpha / r) * B^T (A^T x)``.

Logits: the final-RMSNorm hidden state at the last non-pad token through
``score``. Token id 0 is padding, at the tail. Departures from the published
model are listed under ``assumed`` in the configuration file.

Contractions go through ``nm`` (reference/numerics.py) so that a control can
round their operands; the router (logits, softmax, group limit, top-k), the
attention softmax, the norms and RoPE are float32 in every policy.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# projections that carry an adapter (the configuration's ``assumed.adapters``):
# the attention's five, and gate/up/down of the dense MLP and of the shared
# experts; routed experts and the router carry none
ATTENTION = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
MLP = ("gate_proj", "up_proj", "down_proj")
# a layer's leaf of at least this many elements is fetched for the layer at
# hand, not stacked over the layers (a stack would be a second copy of the
# base)
BIG = 1 << 20


def sizes(cfg: dict, job: dict) -> dict:
    rope = cfg.get("rope_scaling") or {}
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    factor = float(rope.get("factor", 1.0))
    m = (0.1 * float(rope.get("mscale_all_dim", 0.0)) * math.log(factor) + 1.0
         if factor > 1 else 1.0)
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "d_ff": cfg["intermediate_size"], "d_expert": cfg["moe_intermediate_size"],
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "nope": nope, "rope": rot,
        "v_head": cfg["v_head_dim"],
        "held": cfg["n_routed_experts"], "first": cfg["first_expert_held"],
        "router": cfg["router_width"], "shared": cfg["n_shared_experts"],
        "groups": cfg["n_group"], "topk_group": cfg["topk_group"],
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "vocab": cfg["vocab_size"], "classes": cfg["num_labels"],
        "rank": cfg["lora_rank"],
        "scale": float(cfg["lora_alpha"]) / cfg["lora_rank"],
        "eps": float(cfg["rms_norm_eps"]),
        "softmax_scale": (nope + rot) ** -0.5 * m * m,
        "inv_freq": yarn_inv_freq(rot, float(cfg["rope_theta"]), rope),
    }


def yarn_inv_freq(dim: int, theta: float, rope: dict) -> tuple:
    """HF ``DeepseekV2YarnRotaryEmbedding``: dimension i keeps its published
    frequency theta^(-2i/dim) above the correction range, has it divided by
    ``factor`` below, a linear ramp between; the range is where a dimension
    turns ``beta_fast`` .. ``beta_slow`` times over the original context."""
    factor = float(rope.get("factor", 1.0))
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if factor <= 1:
        return tuple(plain)

    def dim_of(rotations):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    out = []
    for i, f in enumerate(plain):
        interpolated = min(max((i - low) / span, 0.0), 1.0)
        out.append(f / factor * interpolated + f * (1.0 - interpolated))
    return tuple(out)


def layer_spec(s: dict, routed: bool) -> dict:
    """name inside a layer -> (shape, init)."""
    d, r, h = s["d"], s["rank"], s["heads"]
    spec = {"input_layernorm/scale": ((d,), "ones"),
            "post_attention_layernorm/scale": ((d,), "ones"),
            "self_attn/q_a_layernorm/scale": ((s["q_rank"],), "ones"),
            "self_attn/kv_a_layernorm/scale": ((s["kv_rank"],), "ones")}

    def proj(name, n_in, n_out, adapted=True):
        spec[f"{name}/kernel"] = ((n_in, n_out), "fan_in")
        if adapted:
            spec[f"{name}/lora_a"] = ((n_in, r), "fan_in")
            spec[f"{name}/lora_b"] = ((r, n_out), "embed")

    def mlp(name, width, adapted=True):
        proj(f"{name}/gate_proj", d, width, adapted)
        proj(f"{name}/up_proj", d, width, adapted)
        proj(f"{name}/down_proj", width, d, adapted)

    proj("self_attn/q_a_proj", d, s["q_rank"])
    proj("self_attn/q_b_proj", s["q_rank"], h * (s["nope"] + s["rope"]))
    proj("self_attn/kv_a_proj_with_mqa", d, s["kv_rank"] + s["rope"])
    proj("self_attn/kv_b_proj", s["kv_rank"], h * (s["nope"] + s["v_head"]))
    proj("self_attn/o_proj", h * s["v_head"], d)
    if routed:
        # the router's matrix by routing group: [groups, d, experts of the
        # group]; expert g * per + e is column e of group g. The generator
        # scales a leaf by all axes but the last, so this layout seeds the
        # logits about N(0, 1 / groups): see the configuration's
        # ``assumed.weights`` for why
        spec["mlp/gate/kernel"] = (
            (s["groups"], d, s["router"] // s["groups"]), "fan_in")
        # one leaf per expert and matrix: the generator scales a matrix by
        # all axes but the last, so a stack over experts would be seeded
        # sqrt(held) too small
        for j in range(s["held"]):
            mlp(f"mlp/experts_{j}", s["d_expert"], adapted=False)
        mlp("mlp/shared_experts", s["shared"] * s["d_expert"])
    else:
        mlp("mlp", s["d_ff"])
    return spec


def param_spec(cfg: dict, job: dict) -> dict:
    """path -> (shape, init). Paths are '/'-joined names."""
    s = sizes(cfg, job)
    spec = {"embed_tokens/embedding": ((s["vocab"], s["d"]), "embed"),
            "norm/scale": ((s["d"],), "ones"),
            "score/kernel": ((s["d"], s["classes"]), "fan_in")}
    for i in range(s["layers"]):
        for name, entry in layer_spec(s, i >= s["dense_layers"]).items():
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


def _swiglu(p, name, u, s, nm):
    gated = jax.nn.silu(_proj(p, f"{name}/gate_proj", u, s, nm)) * _proj(
        p, f"{name}/up_proj", u, s, nm)
    return _proj(p, f"{name}/down_proj", gated, s, nm)


def _rope(x, s):
    """x [B, T, H, rope], halves layout."""
    t = x.shape[1]
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(s["inv_freq"], jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(p, u, pad_mask, s, nm):
    bsz, t, _ = u.shape
    h, nope = s["heads"], s["nope"]
    c_q = _rms_norm(_proj(p, "self_attn/q_a_proj", u, s, nm),
                    p["self_attn/q_a_layernorm/scale"], s["eps"])
    q = _proj(p, "self_attn/q_b_proj", c_q, s, nm).reshape(
        bsz, t, h, nope + s["rope"])
    ckv = _proj(p, "self_attn/kv_a_proj_with_mqa", u, s, nm)
    c_kv = _rms_norm(ckv[..., :s["kv_rank"]],
                     p["self_attn/kv_a_layernorm/scale"], s["eps"])
    k_pe = _rope(ckv[..., None, s["kv_rank"]:], s)  # [B, T, 1, rope]
    kv = _proj(p, "self_attn/kv_b_proj", c_kv, s, nm).reshape(
        bsz, t, h, nope + s["v_head"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], s)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_pe, h, axis=2)], -1)
    scores = nm.einsum("bqhd,bkhd->bhqk", q, k) * s["softmax_scale"]
    keep = (pad_mask[:, None, None, :] > 0) & (
        jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    attn = jax.nn.softmax(
        jnp.where(keep, scores, jnp.finfo(jnp.float32).min), axis=-1)
    out = nm.einsum("bhqk,bkhd->bqhd", attn, kv[..., nope:]).reshape(
        bsz, t, h * s["v_head"])
    return _proj(p, "self_attn/o_proj", out, s, nm)


def route(gate, u, s):
    """u [N, d] -> combine weights [N, router]: routed_scaling_factor * score
    for the chosen experts, 0 elsewhere. float32 in every policy."""
    n, per = u.shape[0], s["router"] // s["groups"]
    scores = jax.nn.softmax(jnp.einsum(
        "nd,gde->nge", u, gate, precision=jax.lax.Precision.HIGHEST
    ).reshape(n, s["router"]), axis=-1)
    group_best = scores.reshape(n, s["groups"], per).max(axis=-1)
    # the topk_group-th largest group score is the bar a group must reach
    bar = jnp.sort(group_best, axis=-1)[:, -s["topk_group"]][:, None]
    kept = jnp.where(jnp.repeat(group_best >= bar, per, axis=1), scores, 0.0)
    bar = jnp.sort(kept, axis=-1)[:, -s["top_k"]][:, None]
    return jnp.where(kept >= bar, kept, 0.0) * s["routed_scale"]


def _moe(p, u, s, nm):
    flat = u.reshape(-1, u.shape[-1])
    combine = route(p["mlp/gate/kernel"], flat, s)
    y = jnp.zeros_like(flat)
    # every held expert over every token, weighted by what the router gave
    # it (0 for a token that did not choose it): plain, and nothing like the
    # program's sorted tiles
    for j in range(s["held"]):
        y = y + combine[:, s["first"] + j, None] * _swiglu(
            p, f"mlp/experts_{j}", flat, s, nm)
    return y.reshape(u.shape) + _swiglu(p, "mlp/shared_experts", u, s, nm)


def _layer(p, h, pad_mask, routed, s, nm):
    u = _rms_norm(h, p["input_layernorm/scale"], s["eps"])
    h = h + _mla(p, u, pad_mask, s, nm)
    u = _rms_norm(h, p["post_attention_layernorm/scale"], s["eps"])
    return h + (_moe(p, u, s, nm) if routed else _swiglu(p, "mlp", u, s, nm))


def _run_of_layers(params, h, pad_mask, layers, routed, s, nm):
    """Consecutive layers of one kind as one ``lax.scan`` (one body compiled,
    not one per layer), each layer rematerialised on the backward pass. A
    layer's small leaves (adapters, norms) are stacked over the layers; its
    large ones are fetched for the layer at hand by ``lax.switch``, one
    layer's copy at a time. Neither changes a value."""
    names = list(layer_spec(s, routed))
    big = [n for n in names if params[f"layers_{layers[0]}/{n}"].size >= BIG]
    stacked = {n: jnp.stack([params[f"layers_{i}/{n}"] for i in layers])
               for n in names if n not in big}
    fetch = [lambda i=i: {n: params[f"layers_{i}/{n}"] for n in big}
             for i in layers]

    @jax.checkpoint
    def body(h_, xs):
        j, small = xs
        p = {**jax.lax.switch(j, fetch), **small}
        return _layer(p, h_, pad_mask, routed, s, nm), None

    h, _ = jax.lax.scan(body, h, (jnp.arange(len(layers)), stacked))
    return h


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """params: flat path -> float32 array. x: int tokens [B, T]. Returns
    float32 logits [B, classes]."""
    s = sizes(cfg, job)
    pad_mask = (x > 0).astype(jnp.float32)
    h = params["embed_tokens/embedding"][x]
    dense = list(range(min(s["dense_layers"], s["layers"])))
    for run, routed in ((dense, False),
                        (list(range(len(dense), s["layers"])), True)):
        if run:
            h = _run_of_layers(params, h, pad_mask, run, routed, s, nm)
    h = _rms_norm(h, params["norm/scale"], s["eps"])
    last = jnp.maximum(pad_mask.sum(axis=1).astype(jnp.int32) - 1, 0)
    pooled = h[jnp.arange(x.shape[0]), last]
    return nm.dot(pooled, params["score/kernel"])
