"""Plain reference of the Nemotron-H sequence classifier: blocks of ONE mixer
each (Mamba-2, grouped-query attention, or a mixture of experts that work in
a latent) read from a pattern string, RMSNorm, LoRA adapters on the
projections, and HF's last-non-pad-token ``score`` head. float32, jax.numpy
only, no kernels; imports nothing of the program.

Block i, kind ``hybrid_override_pattern[i]``: h <- h + Mixer_i(RMSNorm(h)).

  M  Mamba-2 (H heads of P, G groups, state N; head h is in group h // (H/G)):
     [z | xBC | dt] = W_in u;  xBC = silu(conv1d_causal_depthwise(xBC) + b)
     [x | B | C] = split(xBC): x [H, P], B [G, N], C [G, N]
     Delta_t = softplus(dt_t + dt_bias) [H];  a = -exp(A_log) [H]
     S_t = exp(Delta_t a_h) S_{t-1} + Delta_t x_t B_t^T   (S in R^{P x N})
     y_t = S_t C_t + D_h x_t      (ONE POSITION AFTER ANOTHER, a scan over t)
     y = RMSNorm_group(y * silu(z)) (the gate first, then a norm over each
     group's H*P/G lanes, times a scale of H*P);  out = W_out y
  *  Attn: q = W_q u (heads x head_dim), k = W_k u, v = W_v u (kv_heads, each
     repeated over its heads/kv_heads consecutive query heads),
     softmax(q k^T / sqrt(head_dim)) v under the causal and key-padding
     masks, W_o; no positions, no bias
  E  s = sigmoid(u W_r) over ALL router_width experts, float32; chosen = the
     num_experts_per_tok largest of s + b (b the selection bias: it picks and
     does not weigh); w_k = routed_scaling_factor * s_k / (sum_chosen s +
     1e-20); l = W_fc1 u (the latent);  E_j(l) = W_j,down relu(W_j,up l)^2
     out = W_fc2 (sum_{k chosen and held here} w_k E_k(l))
           + W_s,down relu(W_s,up u)^2           (the shared expert, on u)
  W x: every adapted projection is W x + (alpha / r) * B^T (A^T x)

"Held here": the configuration's ``n_routed_experts`` experts from
``first_expert_held`` of the router's ``router_width`` (a chip's share under
expert parallelism). What the absent experts would add is left out, and the
partial result goes on.

Logits: the final-RMSNorm hidden state at the last non-pad token through
``score``. Token id 0 is padding, at the tail. Departures from the published
model are listed under ``assumed`` in the configuration file.

Contractions go through ``nm`` (reference/numerics.py) so that a control can
round their operands; the recurrence, the router (logits, sigmoid, choice,
renormalisation), the norms, the conv, softplus and the softmax are float32
in every policy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# positions of the recurrence rematerialised together on the backward pass: a
# state is H * P * N floats (4 MB at the published widths), one kept a
# segment and a segment's worth while it is differentiated. Changes no value
SEGMENT = 64


def sizes(cfg: dict, job: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {
        "d": cfg["hidden_size"], "pattern": pattern,
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "ssm_heads": cfg["mamba_num_heads"], "ssm_p": cfg["mamba_head_dim"],
        "groups": cfg["n_groups"], "state": cfg["ssm_state_size"],
        "d_conv": cfg["conv_kernel"],
        "held": cfg["n_routed_experts"], "first": cfg["first_expert_held"],
        "router": cfg["router_width"], "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "latent": cfg["moe_latent_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "d_shared": cfg["moe_shared_expert_intermediate_size"],
        "vocab": cfg["vocab_size"], "classes": cfg["num_labels"],
        "rank": cfg["lora_rank"],
        "scale": float(cfg["lora_alpha"]) / cfg["lora_rank"],
        "eps": float(cfg["layer_norm_epsilon"]),
    }


def block_spec(s: dict, kind: str) -> dict:
    """name inside a block -> (shape, init)."""
    d, r = s["d"], s["rank"]
    spec = {"norm/scale": ((d,), "ones")}

    def proj(name, n_in, n_out, adapted=True):
        spec[f"mixer/{name}/kernel"] = ((n_in, n_out), "fan_in")
        if adapted:
            spec[f"mixer/{name}/lora_a"] = ((n_in, r), "fan_in")
            spec[f"mixer/{name}/lora_b"] = ((r, n_out), "embed")

    if kind == MAMBA:
        h, d_inner = s["ssm_heads"], s["ssm_heads"] * s["ssm_p"]
        conv = d_inner + 2 * s["groups"] * s["state"]
        proj("in_proj", d, d_inner + conv + h)
        spec["mixer/conv1d/kernel"] = ((s["d_conv"], conv), "fan_in")
        spec["mixer/conv1d/bias"] = ((conv,), "zeros")
        # a vector's fan-in is 1: A_log is N(0, 1), so the heads' decays
        # a = -exp(A_log) differ (about -0.1 .. -10) and some states reach
        # across many positions and chunks. dt_bias 0: Delta = softplus(dt)
        # with dt about N(0, 1); D 1 (the configuration's assumed.weights)
        spec["mixer/A_log"] = ((h,), "fan_in")
        spec["mixer/dt_bias"] = ((h,), "zeros")
        spec["mixer/D"] = ((h,), "ones")
        spec["mixer/norm/scale"] = ((d_inner,), "ones")
        proj("out_proj", d_inner, d)
    elif kind == ATTENTION:
        hd = s["head_dim"]
        proj("q_proj", d, s["heads"] * hd)
        proj("k_proj", d, s["kv_heads"] * hd)
        proj("v_proj", d, s["kv_heads"] * hd)
        proj("o_proj", s["heads"] * hd, d)
    else:
        proj("gate", d, s["router"], adapted=False)
        # drawn N(0, 0.02^2) and not zeros, so that what picks an expert and
        # what weighs it differ in every run
        spec["mixer/gate/e_score_correction_bias"] = ((s["router"],), "embed")
        # no adapter on the latent projections: their gradient would hang on
        # the discrete picks of the few tokens the loss reads (the
        # configuration's assumed.adapters)
        proj("fc1_latent_proj", d, s["latent"], adapted=False)
        proj("fc2_latent_proj", s["latent"], d, adapted=False)
        # one leaf per expert and matrix: the generator scales a matrix by
        # all axes but the last, so a stack over experts would be seeded
        # sqrt(held) too small
        for j in range(s["held"]):
            proj(f"experts_{j}/up_proj", s["latent"], s["d_expert"], False)
            proj(f"experts_{j}/down_proj", s["d_expert"], s["latent"], False)
        proj("shared_experts/up_proj", d, s["d_shared"])
        proj("shared_experts/down_proj", s["d_shared"], d)
    return spec


def param_spec(cfg: dict, job: dict) -> dict:
    """path -> (shape, init). Paths are '/'-joined names."""
    s = sizes(cfg, job)
    spec = {"embed_tokens/embedding": ((s["vocab"], s["d"]), "embed"),
            "norm_f/scale": ((s["d"],), "ones"),
            "score/kernel": ((s["d"], s["classes"]), "fan_in")}
    for i, kind in enumerate(s["pattern"]):
        for name, entry in block_spec(s, kind).items():
            spec[f"layers_{i}/{name}"] = entry
    return spec


def input_spec(cfg: dict, job: dict) -> dict:
    return {"kind": "tokens", "vocab": cfg["vocab_size"],
            "seq": job["data"]["seq"], "classes": cfg["num_labels"],
            "min_len_frac": job["data"].get("min_len_frac", 1.0)}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _proj(p, name, x, s, nm):
    y = nm.dot(x, p[f"mixer/{name}/kernel"])
    if f"mixer/{name}/lora_a" in p:
        y = y + s["scale"] * nm.dot(nm.dot(x, p[f"mixer/{name}/lora_a"]),
                                    p[f"mixer/{name}/lora_b"])
    return y


def recurrence(x, dt, a, b, c):
    """x [B, T, H, P], dt [B, T, H], a [H], b / c [B, T, G, N] -> y [B, T, H,
    P], y_t = S_t C_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T: one
    position after another, in segments of ``SEGMENT`` positions that are
    rematerialised on the backward pass (no value changes)."""
    bsz, t, h, _ = x.shape
    rep = h // b.shape[2]
    pad = -t % SEGMENT
    time_major = lambda v: jnp.moveaxis(jnp.pad(  # noqa: E731
        v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)), 1, 0).reshape(
        (t + pad) // SEGMENT, SEGMENT, bsz, *v.shape[2:])

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, H, P], [B, H], [B, G, N] x 2
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    state = jnp.zeros((bsz, h, x.shape[3], b.shape[3]), jnp.float32)
    _, y = jax.lax.scan(segment, state,
                        tuple(map(time_major, (x, dt, b, c))))
    return jnp.moveaxis(y.reshape(t + pad, bsz, h, -1), 0, 1)[:, :t]


def _mamba2(p, u, s, nm):
    bsz, t, _ = u.shape
    h, hp, g, n = s["ssm_heads"], s["ssm_p"], s["groups"], s["state"]
    d_inner = h * hp
    z, xbc, dt = jnp.split(_proj(p, "in_proj", u, s, nm),
                           [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (s["d_conv"] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, j:j + t] * p["mixer/conv1d/kernel"][j]
                          for j in range(s["d_conv"]))
                      + p["mixer/conv1d/bias"])
    x = xbc[..., :d_inner].reshape(bsz, t, h, hp)
    b = xbc[..., d_inner:d_inner + g * n].reshape(bsz, t, g, n)
    c = xbc[..., d_inner + g * n:].reshape(bsz, t, g, n)
    dt = jax.nn.softplus(dt + p["mixer/dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["mixer/A_log"]), b, c)
    y = y + p["mixer/D"][:, None] * x
    y = (y.reshape(bsz, t, d_inner) * jax.nn.silu(z)).reshape(bsz, t, g, -1)
    y = _rms_norm(y, p["mixer/norm/scale"].reshape(g, -1), s["eps"])
    return _proj(p, "out_proj", y.reshape(bsz, t, d_inner), s, nm)


def _attention(p, u, pad_mask, s, nm):
    bsz, t, _ = u.shape
    hd, groups = s["head_dim"], s["heads"] // s["kv_heads"]
    q = _proj(p, "q_proj", u, s, nm).reshape(bsz, t, s["heads"], hd)
    k = _proj(p, "k_proj", u, s, nm).reshape(bsz, t, s["kv_heads"], hd)
    v = _proj(p, "v_proj", u, s, nm).reshape(bsz, t, s["kv_heads"], hd)
    # query head h reads key/value head h // groups
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scores = nm.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    keep = (pad_mask[:, None, None, :] > 0) & (
        jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    attn = jax.nn.softmax(
        jnp.where(keep, scores, jnp.finfo(jnp.float32).min), axis=-1)
    out = nm.einsum("bhqk,bkhd->bqhd", attn, v).reshape(bsz, t, s["heads"] * hd)
    return _proj(p, "o_proj", out, s, nm)


def route(p, u, s):
    """u [N, d] -> combine weights [N, router]: routed_scaling_factor * s_k
    / sum_chosen s for the chosen experts, 0 elsewhere. float32 in every
    policy."""
    scores = jax.nn.sigmoid(jnp.matmul(
        u, p["mixer/gate/kernel"], precision=jax.lax.Precision.HIGHEST))
    picking = scores + p["mixer/gate/e_score_correction_bias"]
    # the top_k-th largest of what picks is the bar an expert must reach
    bar = jnp.sort(picking, axis=-1)[:, -s["top_k"]][:, None]
    chosen = jnp.where(picking >= bar, scores, 0.0)
    return s["routed_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _relu2(p, name, x, s, nm):
    return _proj(p, f"{name}/down_proj", jnp.square(jax.nn.relu(
        _proj(p, f"{name}/up_proj", x, s, nm))), s, nm)


def _moe(p, u, s, nm):
    flat = u.reshape(-1, u.shape[-1])
    combine = route(p, flat, s)
    latent = _proj(p, "fc1_latent_proj", flat, s, nm)
    y = jnp.zeros_like(latent)
    # every held expert over every token, weighted by what the router gave
    # it (0 for a token that did not choose it): plain, and nothing like the
    # program's sorted tiles
    for j in range(s["held"]):
        y = y + combine[:, s["first"] + j, None] * _relu2(
            p, f"experts_{j}", latent, s, nm)
    return (_proj(p, "fc2_latent_proj", y, s, nm).reshape(u.shape)
            + _relu2(p, "shared_experts", u, s, nm))


def _block(p, h, pad_mask, kind, s, nm):
    u = _rms_norm(h, p["norm/scale"], s["eps"])
    if kind == MAMBA:
        return h + _mamba2(p, u, s, nm)
    if kind == ATTENTION:
        return h + _attention(p, u, pad_mask, s, nm)
    return h + _moe(p, u, s, nm)


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """params: flat path -> float32 array. x: int tokens [B, T]. Returns
    float32 logits [B, classes]. One block after another, each
    rematerialised on the backward pass (no value changes)."""
    s = sizes(cfg, job)
    pad_mask = (x > 0).astype(jnp.float32)
    h = params["embed_tokens/embedding"][x]
    for i, kind in enumerate(s["pattern"]):
        prefix = f"layers_{i}/"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        h = jax.checkpoint(
            lambda p_, h_, kind=kind: _block(p_, h_, pad_mask, kind, s, nm)
        )(p, h)
    h = _rms_norm(h, params["norm_f/scale"], s["eps"])
    last = jnp.maximum(pad_mask.sum(axis=1).astype(jnp.int32) - 1, 0)
    pooled = h[jnp.arange(x.shape[0]), last]
    return nm.dot(pooled, params["score/kernel"])
