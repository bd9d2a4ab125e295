"""Kernel agreement checks for the Pallas kernels (flash attention, DP clip,
the chunked scalar-decay scan, the routed layer's row combine).

The kernels are interpret-mode validated by the CPU suite
(tests/kernels/), but a Mosaic compile can fail or miscompute where
interpret mode passes. These checks run the REAL compiled kernels on the
attached TPU against dense XLA references on the same device.

``chip_smoke.py`` (repo root) calls :func:`run_checks` in its own process as
its kernel stage — that is how the checks normally run on the chip. By hand,
``python tools/tpu_selftest.py`` prints ONE JSON line

  {"ok": bool, "platform": ..., "device_kind": ..., "checks": [...]}

and exits 0 iff every check passed. On the CPU backend the kernels pick
interpret mode themselves, which validates this file's own reference math
and tolerances (``toy=True`` keeps that affordable), so a failure on the
chip can only mean Mosaic.

Reference contract being validated (no reference-repo counterpart — the
reference delegates attention to torch SDPA and DP clipping to Opacus;
SURVEY.md §2.0): numerical agreement of the fused kernels with the naive
formulation, forward AND backward, directly and the way the engine calls
them (under ``vmap`` over clients, ``jax.checkpoint`` and ``custom_vjp``).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _check(name: str, fn) -> dict:
    try:
        ok, detail = fn()
        return {"name": name, "ok": bool(ok), "detail": detail}
    except Exception as e:  # noqa: BLE001 — a Mosaic compile error IS the finding
        return {"name": name, "ok": False, "detail": f"{type(e).__name__}: {e}"}


def _dense_ref(q, k, v, mask):
    import jax
    import jax.numpy as jnp

    scale = 1.0 / (q.shape[-1] ** 0.5)
    # HIGHEST: on TPU the default lowers f32 matmuls to one bf16 MXU pass
    # (~1e-3 abs err) — the reference must be faithful f32 or the f32
    # tolerance below just measures the reference's own sloppiness
    prec = jax.lax.Precision.HIGHEST
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    # [B,T,H,D] -> scores [B,H,Tq,Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * scale
    s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=prec)


def _inputs(b, t, h, d, dtype, frac_pad=0.25):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), dtype) for kk in ks)
    n_real = int(t * (1 - frac_pad))
    mask = (jnp.arange(t)[None, :] < n_real).astype(jnp.float32)
    return q, k, v, jnp.broadcast_to(mask, (b, t)), n_real


def _rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-6))


def _dense_window_ref(q, k, v, mask, window, block):
    """Causal attention under a sliding window over grouped key/value heads
    (query head h reads key head ``h // rep``) with the scores
    materialised, ``block`` queries at a time against the keys they can see
    at all, each block rematerialised on the way back and the sequences one
    after another, so that [B, H, T, T] never exists (4 x 32 x 8,192^2
    float32 would be 34 GB); the mask inside a block is the plain one."""
    import jax
    import jax.numpy as jnp

    prec = jax.lax.Precision.HIGHEST
    t, d = q.shape[1], q.shape[-1]
    rep = q.shape[2] // k.shape[2]

    @jax.checkpoint
    def rows(qb, kb, vb, maskb, q0, k0):
        i = q0 + jnp.arange(qb.shape[0])[:, None]
        j = k0 + jnp.arange(kb.shape[0])[None, :]
        keep = (j <= i) & (i - j < window) & (maskb[None, :] > 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kb, precision=prec) / (d ** 0.5)
        p = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb, precision=prec)

    def one(args):
        q, k, v, mask = args
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        out = []
        for q0 in range(0, t, block):
            q1, k0 = min(q0 + block, t), max(0, q0 - window + 1)
            out.append(rows(q[q0:q1], k[k0:q1], v[k0:q1], mask[k0:q1], q0,
                            k0))
        return jnp.concatenate(out, axis=0)

    return jax.lax.map(one, (q, k, v, mask))


def flash_checks(toy: bool = False) -> list[dict]:
    """Flash attention vs dense attention. ``toy`` keeps one forward case,
    the engine-shaped backward case and the two rejections, at shapes (and
    blocks) interpret mode runs in seconds on CPU."""
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.kernels.flash_attention import flash_attention

    checks = []
    blk = 16 if toy else 128  # the unit every block size below scales from

    def fwd_case(t, d, dtype, tol, name, b=2, h=4):
        def run():
            q, k, v, mask, n_real = _inputs(b, t, h, d, dtype)
            out = jax.jit(
                lambda *a: flash_attention(*a, block_q=blk, block_k=blk)
            )(q, k, v, mask)
            ref = jax.jit(_dense_ref)(q, k, v, mask)
            # padded query rows attend to garbage by design; compare real rows
            err = float(jnp.max(jnp.abs(
                out[:, :n_real].astype(jnp.float32) - ref[:, :n_real])))
            return err < tol, f"max_abs_err={err:.2e} tol={tol}"
        checks.append(_check(name, run))

    def grad_case(t, d, dtype, bq, bk, tol, name, b=2, h=4,
                  wrap=lambda f: f):
        """Forward + dQ/dK/dV against the dense reference, relative to the
        reference's largest gradient (bf16 gradients are O(10) here, so an
        absolute tolerance would just measure their magnitude)."""
        def run():
            q, k, v, mask, _ = _inputs(b, t, h, d, dtype)
            w = mask[:, :, None, None]

            def loss(attn):
                def f(q, k, v):
                    o = attn(q, k, v).astype(jnp.float32)
                    return jnp.sum(o * o * w)
                return f

            def flash(q, k, v):
                return flash_attention(q, k, v, mask, block_q=bq, block_k=bk)

            g_f, g_r = (
                jax.jit(jax.grad(wrap(loss(attn)), argnums=(0, 1, 2)))(q, k, v)
                for attn in (flash, lambda q, k, v: _dense_ref(q, k, v, mask))
            )
            errs = [_rel_err(a, b_) for a, b_ in zip(g_f, g_r)]
            return (max(errs) < tol,
                    f"rel grad errs dq/dk/dv={[f'{e:.1e}' for e in errs]} "
                    f"tol={tol}")
        checks.append(_check(name, run))

    t = 64 if toy else 512
    fwd_case(t, 64, jnp.float32, 2e-4, "flash_fwd_f32")
    # the way the engine reaches the kernel: vmap over clients of
    # grad(checkpoint(custom_vjp)) — pallas_call's batching rule adds a grid
    # axis, remat replays the forward kernel inside the backward pass
    grad_case(t, 64, jnp.float32, blk, blk, 1e-3,
              "flash_bwd_f32_vmap_remat", b=1,
              wrap=lambda f: (lambda q, k, v: jnp.sum(jax.vmap(
                  jax.checkpoint(f))(jnp.stack([q, 0.5 * q]),
                                     jnp.stack([k, k]),
                                     jnp.stack([v, 2.0 * v])))))
    if not toy:
        fwd_case(2048, 64, jnp.bfloat16, 3e-2, "flash_fwd_bf16_t2048")
        # T=600 does NOT divide the block -> real zero-padding to 640 plus
        # key-block tail masking
        fwd_case(600, 64, jnp.float32, 2e-4, "flash_fwd_f32_t600_ragged")
        grad_case(512, 64, jnp.float32, 128, 128, 1e-3, "flash_bwd_f32_t512")
        # every block family the compiled kernel accepts: multiples of 128,
        # mixed, and one block spanning the whole sequence
        for bq, bk in ((256, 256), (128, 256), (512, 512)):
            grad_case(1024, 64, jnp.bfloat16, bq, bk, 3e-2,
                      f"flash_bwd_bf16_block_{bq}_{bk}")
        grad_case(16, 8, jnp.float32, 16, 16, 1e-3,
                  "flash_bwd_f32_whole_seq_block")
        # the largest whole-sequence K/V pairs the compiler accepts
        # (kernels/flash_attention.py _VMEM_PAIR_BYTES)
        grad_case(16384, 64, jnp.bfloat16, 128, 128, 3e-2,
                  "flash_bwd_bf16_t16384_vmem_limit", b=1, h=2)
        grad_case(8192, 128, jnp.float32, 128, 128, 1e-3,
                  "flash_bwd_f32_t8192_d128_vmem_limit", b=1, h=2)

    def window_case(b, t, h, kv, d, dtype, block, window, tol, name):
        """The three calls under a sliding window over grouped key/value
        heads (``models/afmoe.py``'s sliding layers) against dense masked
        attention: the forward's real rows, and dQ / dK / dV relative to the
        reference's largest gradient."""
        def run():
            ks = jax.random.split(jax.random.PRNGKey(2), 3)
            q, k, v = (jax.random.normal(kk, (b, t, n, d), dtype)
                       for kk, n in zip(ks, (h, kv, kv)))
            n_real = t - t // 8
            mask = jnp.broadcast_to(
                (jnp.arange(t)[None, :] < n_real).astype(jnp.float32), (b, t))
            w = mask[:, :, None, None]

            def flash(q, k, v):
                return flash_attention(q, k, v, mask, block_q=block,
                                       block_k=block, causal=True,
                                       window=window)

            def dense(q, k, v):
                return _dense_window_ref(q, k, v, mask, window, block)

            def loss(attn):
                def f(q, k, v):
                    o = attn(q, k, v).astype(jnp.float32)
                    return jnp.sum(o * o * w), o
                return f

            (g_f, o_f), (g_r, o_r) = (
                jax.jit(jax.grad(loss(attn), argnums=(0, 1, 2),
                                 has_aux=True))(q, k, v)
                for attn in (flash, dense))
            fwd = float(jnp.max(jnp.abs((o_f - o_r) * w)))
            errs = [_rel_err(a, b_) for a, b_ in zip(g_f, g_r)]
            return (max([fwd] + errs) < tol,
                    f"fwd max_abs_err={fwd:.1e} rel grad errs dq/dk/dv="
                    f"{[f'{e:.1e}' for e in errs]} tol={tol}")
        checks.append(_check(name, run))

    if toy:
        window_case(2, 64, 4, 2, 128, jnp.float32, blk, 24, 1e-3,
                    "flash_window_f32_grouped")
    else:
        # the sliding layers of the trinity_mini cell: 4 sequences of 8,192,
        # 32 query heads over 4 key/value heads of 128, a window of 2,048
        window_case(4, 8192, 32, 4, 128, jnp.bfloat16, 512, 2048, 3e-2,
                    "flash_window_bf16_t8192_w2048_grouped")

    def tile_probe(b, t, h, kv, d, block, window, causal, name):
        """Microseconds an executed score tile of the forward and of the
        three calls together, by the host's clock around whole calls (PR 44:
        what a causal call's traversal costs beside the straight-line body of
        a call that is not causal, at the trinity_mini cell's shape). On the
        CPU nothing is timed: the detail holds the tile count alone."""
        def run():
            import time

            from fl4health_tpu.kernels.flash_attention import live_tiles

            ks = jax.random.split(jax.random.PRNGKey(3), 3)
            q, k, v = (jax.random.normal(kk, (b, t, n, d), jnp.bfloat16)
                       for kk, n in zip(ks, (h, kv, kv)))

            def flash(q, k, v):
                return flash_attention(q, k, v, None, block_q=block,
                                       block_k=block, causal=causal,
                                       window=window)

            def loss(q, k, v):
                o = flash(q, k, v).astype(jnp.float32)
                return jnp.sum(o * o)

            fwd = jax.jit(flash)
            all3 = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            tiles = b * h * (live_tiles(t, block, block, window) if causal
                             else (t // block) ** 2)
            out = jax.block_until_ready((fwd(q, k, v), all3(q, k, v)))
            finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                         for x in jax.tree_util.tree_leaves(out))
            if jax.default_backend() != "tpu":
                return finite, f"tiles={tiles} (not timed off the chip)"

            def ms(fn, n=10):
                jax.block_until_ready(fn(q, k, v))
                t0 = time.perf_counter()
                jax.block_until_ready([fn(q, k, v) for _ in range(n)])
                return (time.perf_counter() - t0) * 1e3 / n

            f_ms, a_ms = ms(fwd), ms(all3)
            return finite, (
                f"tiles={tiles} fwd {f_ms:.2f} ms = {f_ms * 1e3 / tiles:.3f} "
                f"us/tile; fwd+dq+dkv {a_ms:.2f} ms = "
                f"{a_ms * 1e3 / tiles:.3f} us/tile")
        checks.append(_check(name, run))

    # the trinity_mini cell's calls three ways: every tile in one
    # straight-line body, the causal triangle, the band of a 2,048 window
    probe = ((2, 64, 4, 2, 128, blk, 24) if toy
             else (4, 8192, 32, 4, 128, 512, 2048))
    tile_probe(*probe[:6], None, False, "flash_tile_probe_not_causal")
    tile_probe(*probe[:6], None, True, "flash_tile_probe_causal")
    tile_probe(*probe, True, "flash_tile_probe_window")

    def rejected(name, t, d, dtype, block):
        """A request Mosaic cannot compile must fail in Python, naming the
        reason — checked with interpret=False so it runs anywhere."""
        def run():
            x = jnp.zeros((1, t, 1, d), dtype)
            try:
                jax.eval_shape(lambda x: flash_attention(
                    x, x, x, block_q=block, block_k=block, interpret=False), x)
            except ValueError as e:
                return True, f"rejected: {str(e)[:80]}..."
            return False, "accepted a request the compiler refuses"
        checks.append(_check(name, run))

    rejected("flash_block64_rejected", 512, 64, jnp.bfloat16, 64)
    rejected("flash_t16384_f32_d128_rejected", 16384, 128, jnp.float32, 128)
    return checks


def dp_clip_checks(toy: bool = False) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.kernels.dp_clip import fused_clipped_masked_sum

    b, rows = (8, 16) if toy else (64, 256)
    bound = 1.0
    prec = jax.lax.Precision.HIGHEST

    def make(lead):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        grads = {
            "w": jax.random.normal(ks[0], lead + (b, rows, 130)),  # ragged width
            "b": jax.random.normal(ks[1], lead + (b, 130)),
        }
        mask = (jax.random.uniform(ks[2], lead + (b,)) > 0.3).astype(jnp.float32)
        return grads, mask

    def reference(grads, mask):
        flat = jnp.concatenate(
            [grads["w"].reshape(b, -1), grads["b"].reshape(b, -1)], axis=1)
        norms = jnp.linalg.norm(flat, axis=1)
        factor = jnp.minimum(1.0, bound / jnp.maximum(norms, 1e-12)) * mask
        return {"w": jnp.einsum("b,bij->ij", factor, grads["w"], precision=prec),
                "b": jnp.einsum("b,bi->i", factor, grads["b"], precision=prec)}

    def fused(grads, mask):
        return fused_clipped_masked_sum(grads, mask, bound)

    def agree(out, ref):
        err = max(float(jnp.max(jnp.abs(out[k] - ref[k]))) for k in ("w", "b"))
        tol = 1e-4
        return err < tol, f"max_abs_err={err:.2e} tol={tol}"

    def direct():
        grads, mask = make(())
        return agree(jax.jit(fused)(grads, mask), jax.jit(reference)(grads, mask))

    def vmapped():
        # over a clients axis — how the engine would call it
        grads, mask = make((4,))
        return agree(jax.jit(jax.vmap(fused))(grads, mask),
                     jax.jit(jax.vmap(reference))(grads, mask))

    checks = [_check("dp_clip_fused_vmap_clients", vmapped)]
    if not toy:
        checks.append(_check("dp_clip_fused_b64", direct))
    return checks


def ssd_scan_checks(toy: bool = False) -> list[dict]:
    """The chunked scalar-decay scan's Mosaic calls against its ``jnp`` form,
    forward and every gradient, four vmapped clients at a length that is no
    multiple of the chunk. float32 operands agree to summation order (2e-4
    holds the decays' gradient, a sum over every position that cancels; one
    bfloat16 pass in a product is 1e-3 and more); bfloat16 gradients leave in
    bfloat16 (a unit in the last place is 0.4-0.8 %)."""
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.kernels.ssd_scan import ssd_scan, ssd_scan_xla

    heads, groups = (4, 2) if toy else (32, 2)

    def case(dtype, tol, name):
        def run():
            keys = jax.random.split(jax.random.PRNGKey(0), 6)
            lead, t = (4, 1), 328
            n = jax.random.normal
            ops = (n(keys[0], (*lead, t, heads, 64)).astype(dtype),
                   jax.nn.softplus(n(keys[1], (*lead, t, heads))),
                   -jnp.exp(n(keys[2], (heads,))),
                   n(keys[3], (*lead, t, groups, 128)).astype(dtype),
                   n(keys[4], (*lead, t, groups, 128)).astype(dtype))
            cot = n(keys[5], ops[0].shape)

            def both(scan):
                clients = jax.vmap(lambda *o: scan(*o, 128),
                                   in_axes=(0, 0, None, 0, 0))
                with jax.default_matmul_precision("highest"):
                    y, vjp = jax.vjp(clients, *ops)
                    return (y, *vjp(cot))

            errs = [_rel_err(g, w)
                    for g, w in zip(both(ssd_scan), both(ssd_scan_xla))]
            return max(errs) < tol, f"rel_errs={[f'{e:.2e}' for e in errs]}"

        return _check(name, run)

    return [case(jnp.float32, 2e-4, "ssd_scan_f32_t328_h%d" % heads),
            case(jnp.bfloat16, 2e-2, "ssd_scan_bf16_t328_h%d" % heads)]


def _device_us(fn, *args, n: int = 5, carry: bool = False) -> float:
    """Microseconds one call of the jitted ``fn`` keeps the device busy: the
    union of the ``XLA Ops`` events of ``n`` calls under ``jax.profiler``
    (the device's clock, so a short op is not read as its dispatch).
    ``carry``: the result is the next call's first argument (a donated
    buffer updated in place)."""
    import shutil
    import tempfile

    import jax

    from benchmarks import trace_reduce

    def call(args):
        out = jax.block_until_ready(fn(*args))
        return ((out, *args[1:]) if carry else args)

    args = call(args)
    where = tempfile.mkdtemp(prefix="routed_rows_probe_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(where, profiler_options=opts)
        try:
            for _ in range(n):
                args = call(args)
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(where))
        return trace.busy_s() * 1e6 / n
    finally:
        shutil.rmtree(where, ignore_errors=True)


def _rows_only(x, *matrices):
    """An expert body without its products: what is left is the rows'
    travel."""
    return x


def routed_rows_checks(toy: bool = False) -> list[dict]:
    """How the routed layer's held rows travel, at the trinity_mini cell's
    shape (``x [32,768, 2,048]`` bfloat16, the 8 best of 128 by a seeded
    score, 16 held, a pad tail picking none): device microseconds a held row
    of ``models/routed.py``'s forward and backward, whole and with the
    body's products taken out, of ONE long gather and ONE long scatter-add
    of as many rows (PR 45's step 0), and of the layer's own combine, the
    Mosaic call ``kernels/row_combine.py add_rows``. Readings, not limits:
    the checks fail only on a result that is not finite, a forward that
    leaves the plain loop or a combine that is not XLA's scatter-add to the
    bit. On the CPU nothing is timed."""
    import functools

    import jax
    import jax.numpy as jnp

    from fl4health_tpu.kernels import row_combine
    from fl4health_tpu.models import routed

    n, d, f, width, top_k, held = ((96, 16, 8, 16, 4, 4) if toy else
                                   (32768, 2048, 1024, 128, 8, 16))
    keys = iter(jax.random.split(jax.random.PRNGKey(45), 10 + 3 * held))
    real = n - n // 4  # the tail of a seed's documents: pads pick no expert
    idx = jax.lax.top_k(jax.random.uniform(next(keys), (n, width)), top_k)[1]
    idx = jnp.where((jnp.arange(n) < real)[:, None], idx, -1).astype(
        jnp.int32)
    w = jax.random.uniform(next(keys), (n, top_k))
    x = jax.random.normal(next(keys), (n, d), jnp.bfloat16)
    dy = jax.random.normal(next(keys), (n, d))
    experts = [tuple(jax.random.normal(next(keys), s, jnp.bfloat16) / 32
                     for s in ((d, f), (d, f), (f, d))) for _ in range(held)]
    flat = [m for mats in experts for m in mats]
    rows = int(jnp.sum((idx >= 0) & (idx < held)))
    timed = jax.default_backend() == "tpu"
    checks = []

    def reading(name, fn, *args, per=rows, carry=False):
        def run():
            out = jax.block_until_ready(fn(*args))
            finite = all(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                         for a in jax.tree_util.tree_leaves(out))
            if not timed:
                return finite, f"rows={per} (not timed off the chip)"
            us = _device_us(fn, *((out, *args[1:]) if carry else args),
                            carry=carry)
            return finite, (f"rows={per} {us / 1e3:.3f} ms = "
                            f"{us / per:.4f} us/row")
        checks.append(_check(name, run))

    forward = {label: jax.jit(functools.partial(routed._routed_fwd, 0, body, 3))
               for label, body in (("whole", routed.swiglu_expert),
                                   ("rows_only", _rows_only))}
    for label, body in (("whole", routed.swiglu_expert), ("rows_only",
                                                      _rows_only)):
        reading(f"routed_rows_probe_forward_{label}", forward[label], x, idx,
                w, *flat)
        reading(f"routed_rows_probe_backward_{label}", jax.jit(
            functools.partial(routed._routed_bwd, 0, body, 3)), x, idx, w, dy,
            *flat)

    def agrees():
        got = forward["whole"](x, idx, w, *flat)
        want = jnp.zeros((n, d), jnp.float32)
        for j, (gate, up, down) in enumerate(experts):
            combine = jnp.sum(jnp.where(idx == j, w, 0.0), axis=1)
            want = want + combine[:, None] * (
                (jax.nn.silu(x @ gate) * (x @ up)) @ down).astype(jnp.float32)
        return _rel_err(got, want) < 2e-2, f"rel_err={_rel_err(got, want):.2e}"
    checks.append(_check("routed_rows_forward_agrees_with_plain_loop",
                         agrees))

    # ONE gather and ONE combine of a chunk's and of a pass's rows, the
    # tokens as the plan leaves them: ascending inside an expert
    _, tok, _, _, _ = routed._plan(idx, w, 0, held)
    for count in sorted({min(rows, 4096), rows}):
        t = tok[:count]
        reading(f"routed_rows_probe_gather_bf16_{count}",
                jax.jit(lambda x, t: x[t]), x, t, per=count)
        reading(f"routed_rows_probe_gather_f32_{count}",
                jax.jit(lambda a, t: a[t]), dy, t, per=count)
        reading(f"routed_rows_probe_scatter_add_{count}",
                jax.jit(lambda y, t, u: y.at[t].add(u), donate_argnums=0),
                jnp.zeros((n, d), jnp.float32), t, dy[:count], per=count,
                carry=True)

    # the layer's own combine (``kernels/row_combine.py``): a chunk's rows,
    # unique inside a tile, some dead, the same token free to come again in
    # the next tile; against XLA's scatter-add, to the bit
    tile = 8 if toy else routed.TILE_ROWS
    count = 4 * tile if toy else 4096
    slab = row_combine.slab(128 if toy else d)
    t = jnp.concatenate([
        jax.random.permutation(k, n)[:tile]
        for k in jax.random.split(next(keys), count // tile)]).astype(
            jnp.int32)
    t = jnp.where(jnp.arange(count) % 5 == 0, n, t)
    u = jax.random.normal(next(keys), (count, *slab))
    combine = jax.jit(lambda y, t, u: row_combine.add_rows(y, t, u, tile),
                      donate_argnums=0)

    def same():
        got = combine(jnp.ones((n, *slab), jnp.float32), t, u)
        want = jnp.ones((n, *slab), jnp.float32).at[t].add(u, mode="drop")
        return bool(jnp.array_equal(got, want)), (
            f"largest gap {float(jnp.abs(got - want).max()):.1e}")
    checks.append(_check("routed_rows_add_rows_is_a_scatter_add", same))
    reading(f"routed_rows_probe_add_rows_{count}", combine,
            jnp.zeros((n, *slab), jnp.float32), t, u, per=count, carry=True)
    return checks


def run_checks(toy: bool = False) -> list[dict]:
    return (flash_checks(toy) + dp_clip_checks(toy) + ssd_scan_checks(toy)
            + routed_rows_checks(toy))


def main() -> int:
    import jax

    d = jax.devices()[0]
    record = {
        "platform": d.platform,
        "device_kind": getattr(d, "device_kind", "unknown"),
        "checks": run_checks(toy=d.platform == "cpu"),
    }
    record["ok"] = all(c["ok"] for c in record["checks"])
    print(json.dumps(record))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
