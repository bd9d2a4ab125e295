"""Plain reference of the Jamba sequence classifier: a hybrid of Mamba-1
mixers and causal attention with one shared key/value head, a dense SwiGLU
MLP in every layer, RMSNorm, LoRA adapters on the projections, and HF
``JambaForSequenceClassification``'s head. float32, jax.numpy only, no
kernels; imports nothing of the program.

Layer l: h <- h + Mixer_l(RMSNorm(h)); h <- h + MLP(RMSNorm(h)). The mixer is
attention where l % attn_layer_period == attn_layer_offset, Mamba elsewhere.

  MLP(u)   = W_down(silu(W_gate u) * W_up u)
  Mamba(u) : [x, z] = split(W_in u); x = silu(conv1d_causal_depthwise(x) + b)
             [delta, B, C] = split(W_x x), each through its RMSNorm
             Delta = softplus(W_dt delta + b_dt); A = -exp(A_log)
             s_t = exp(Delta_t * A) * s_{t-1} + (Delta_t * x_t) (x) B_t
             y_t = s_t . C_t + D * x_t          (a sequential scan over t)
             out = W_out(y * silu(z))
  Attn(u)  : q = W_q u (heads x 128), k = W_k u, v = W_v u (one head, shared),
             softmax(q k^T / sqrt(128)) v under the causal and key-padding
             masks, W_o; no positions, no bias
  W x      : every adapted projection is W x + (alpha / r) * B^T (A^T x)

Logits: the final-RMSNorm hidden state at the last non-pad token through
``score``. Token id 0 is padding, at the tail. Departures from the published
model are listed under ``assumed`` in the configuration file.

Contractions go through ``nm`` (reference/numerics.py) so that a control can
round their operands; the recurrence, the norms, the conv, softplus and the
softmax are float32 in every policy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# projections that carry an adapter (the configuration's ``assumed.adapters``)
ADAPTED = ("in_proj", "x_proj", "out_proj", "gate_proj", "up_proj",
           "down_proj", "q_proj", "k_proj", "v_proj")
# a layer's leaf of at least this many elements is fetched for the layer at
# hand, not stacked over the layers (a stack would be a second copy of the
# base: 5.7 GB beside the 6.4 GB it is made from)
BIG = 1 << 20


def sizes(cfg: dict, job: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return {
        "d": d, "layers": cfg["num_hidden_layers"], "d_ff": cfg["intermediate_size"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": d // heads, "d_inner": cfg["mamba_expand"] * d,
        "d_state": cfg["mamba_d_state"], "dt_rank": cfg["mamba_dt_rank"],
        "d_conv": cfg["mamba_d_conv"], "vocab": cfg["vocab_size"],
        "classes": cfg["num_labels"], "rank": cfg["lora_rank"],
        "scale": float(cfg["lora_alpha"]) / cfg["lora_rank"],
        "eps": float(cfg["rms_norm_eps"]),
        "period": cfg["attn_layer_period"], "offset": cfg["attn_layer_offset"],
    }


def is_attention(s: dict, layer: int) -> bool:
    return layer % s["period"] == s["offset"]


def layer_spec(s: dict, attention: bool) -> dict:
    """name inside a layer -> (shape, init)."""
    d, f, r = s["d"], s["d_ff"], s["rank"]
    spec = {"input_layernorm/scale": ((d,), "ones"),
            "pre_ff_layernorm/scale": ((d,), "ones")}

    def proj(name, n_in, n_out):
        spec[f"{name}/kernel"] = ((n_in, n_out), "fan_in")
        if name.rsplit("/", 1)[-1] in ADAPTED:
            spec[f"{name}/lora_a"] = ((n_in, r), "fan_in")
            spec[f"{name}/lora_b"] = ((r, n_out), "embed")

    if attention:
        hd = s["head_dim"]
        proj("self_attn/q_proj", d, s["heads"] * hd)
        proj("self_attn/k_proj", d, s["kv_heads"] * hd)
        proj("self_attn/v_proj", d, s["kv_heads"] * hd)
        proj("self_attn/o_proj", s["heads"] * hd, d)
    else:
        di, n, rk = s["d_inner"], s["d_state"], s["dt_rank"]
        proj("mamba/in_proj", d, 2 * di)
        spec["mamba/conv1d/kernel"] = ((s["d_conv"], di), "fan_in")
        spec["mamba/conv1d/bias"] = ((di,), "zeros")
        proj("mamba/x_proj", di, rk + 2 * n)
        spec["mamba/dt_layernorm/scale"] = ((rk,), "ones")
        spec["mamba/b_layernorm/scale"] = ((n,), "ones")
        # C's scale is drawn N(0, 0.02^2): the recurrence's term of y is then
        # a small part of it beside D * x, as a residual branch is of its
        # stream after a depth-scaled init. With a scale of ones the seeded
        # model amplifies a rounding error about 1.25x a layer, and no limit
        # tells bfloat16 from float8 (the configuration's ``assumed.weights``)
        spec["mamba/c_layernorm/scale"] = ((n,), "embed")
        spec["mamba/dt_proj/kernel"] = ((rk, di), "fan_in")
        spec["mamba/dt_proj/bias"] = ((di,), "zeros")
        spec["mamba/A_log"] = ((di, n), "fan_in")
        spec["mamba/D"] = ((di,), "ones")
        proj("mamba/out_proj", di, d)
    proj("feed_forward/gate_proj", d, f)
    proj("feed_forward/up_proj", d, f)
    proj("feed_forward/down_proj", f, d)
    return spec


def param_spec(cfg: dict, job: dict) -> dict:
    """path -> (shape, init). Paths are '/'-joined names."""
    s = sizes(cfg, job)
    spec = {"embed_tokens/embedding": ((s["vocab"], s["d"]), "embed"),
            "final_layernorm/scale": ((s["d"],), "ones"),
            "score/kernel": ((s["d"], s["classes"]), "fan_in")}
    for i in range(s["layers"]):
        for name, entry in layer_spec(s, is_attention(s, i)).items():
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


def _mamba(p, u, s, nm):
    t = u.shape[1]
    x, z = jnp.split(_proj(p, "mamba/in_proj", u, s, nm), 2, axis=-1)
    xp = jnp.pad(x, ((0, 0), (s["d_conv"] - 1, 0), (0, 0)))
    x = sum(xp[:, j:j + t] * p["mamba/conv1d/kernel"][j]
            for j in range(s["d_conv"])) + p["mamba/conv1d/bias"]
    x = jax.nn.silu(x)
    delta, b, c = jnp.split(
        _proj(p, "mamba/x_proj", x, s, nm),
        [s["dt_rank"], s["dt_rank"] + s["d_state"]], axis=-1)
    delta = _rms_norm(delta, p["mamba/dt_layernorm/scale"], s["eps"])
    b = _rms_norm(b, p["mamba/b_layernorm/scale"], s["eps"])
    c = _rms_norm(c, p["mamba/c_layernorm/scale"], s["eps"])
    dt = jax.nn.softplus(nm.dot(delta, p["mamba/dt_proj/kernel"])
                         + p["mamba/dt_proj/bias"])
    a = -jnp.exp(p["mamba/A_log"])  # [d_inner, d_state]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    time_major = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    # one position after another; eight to a loop trip, which changes no value
    _, y = jax.lax.scan(step, jnp.zeros((u.shape[0], *a.shape), jnp.float32),
                        tuple(map(time_major, (x, dt, b, c))), unroll=8)
    y = (time_major(y) + p["mamba/D"] * x) * jax.nn.silu(z)
    return _proj(p, "mamba/out_proj", y, s, nm)


def _attention(p, u, pad_mask, s, nm):
    bsz, t, _ = u.shape
    hd = s["head_dim"]
    q = _proj(p, "self_attn/q_proj", u, s, nm).reshape(bsz, t, s["heads"], hd)
    k = _proj(p, "self_attn/k_proj", u, s, nm).reshape(bsz, t, s["kv_heads"], hd)
    v = _proj(p, "self_attn/v_proj", u, s, nm).reshape(bsz, t, s["kv_heads"], hd)
    groups = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scores = nm.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    keep = (pad_mask[:, None, None, :] > 0) & (
        jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]
    attn = jax.nn.softmax(
        jnp.where(keep, scores, jnp.finfo(jnp.float32).min), axis=-1)
    out = nm.einsum("bhqk,bkhd->bqhd", attn, v).reshape(bsz, t, s["heads"] * hd)
    return _proj(p, "self_attn/o_proj", out, s, nm)


def _layer(p, h, pad_mask, attention, s, nm):
    u = _rms_norm(h, p["input_layernorm/scale"], s["eps"])
    h = h + (_attention(p, u, pad_mask, s, nm) if attention
             else _mamba(p, u, s, nm))
    u = _rms_norm(h, p["pre_ff_layernorm/scale"], s["eps"])
    gated = jax.nn.silu(_proj(p, "feed_forward/gate_proj", u, s, nm)) * _proj(
        p, "feed_forward/up_proj", u, s, nm)
    return h + _proj(p, "feed_forward/down_proj", gated, s, nm)


def _run_of_layers(params, h, pad_mask, layers, attention, s, nm):
    """Consecutive layers of one kind as one ``lax.scan`` (one body compiled,
    not one per layer), each layer rematerialised on the backward pass. A
    layer's small leaves (adapters, norms) are stacked over the layers; its
    large ones are fetched for the layer at hand by ``lax.switch``, one
    layer's copy at a time. Neither changes a value."""
    names = list(layer_spec(s, attention))
    big = [n for n in names if params[f"layers_{layers[0]}/{n}"].size >= BIG]
    stacked = {n: jnp.stack([params[f"layers_{i}/{n}"] for i in layers])
               for n in names if n not in big}
    fetch = [lambda i=i: {n: params[f"layers_{i}/{n}"] for n in big}
             for i in layers]

    @jax.checkpoint
    def body(h_, xs):
        j, small = xs
        p = {**jax.lax.switch(j, fetch), **small}
        return _layer(p, h_, pad_mask, attention, s, nm), None

    h, _ = jax.lax.scan(body, h, (jnp.arange(len(layers)), stacked))
    return h


def forward(params: dict, x, cfg: dict, job: dict, nm):
    """params: flat path -> float32 array. x: int tokens [B, T]. Returns
    float32 logits [B, classes]."""
    s = sizes(cfg, job)
    pad_mask = (x > 0).astype(jnp.float32)
    h = params["embed_tokens/embedding"][x]
    run: list[int] = []
    for i in range(s["layers"] + 1):
        if run and (i == s["layers"]
                    or is_attention(s, i) != is_attention(s, run[0])):
            h = _run_of_layers(params, h, pad_mask, run,
                               is_attention(s, run[0]), s, nm)
            run = []
        run.append(i)
    h = _rms_norm(h, params["final_layernorm/scale"], s["eps"])
    last = jnp.maximum(pad_mask.sum(axis=1).astype(jnp.int32) - 1, 0)
    pooled = h[jnp.arange(x.shape[0]), last]
    return nm.dot(pooled, params["score/kernel"])
