"""chip_smoke.py — does ``FederatedSimulation.fit()`` still start on the chip?

The quickest end-to-end proof, run through the chip tool as the first command
of any chip session::

    chiprun -- python3 chip_smoke.py                 # one chip
    chiprun --chips 4 -- python3 chip_smoke.py       # adds the mesh stage

ONE process, no children, no probe. Every training stage goes through the
entry point users call — ``FederatedSimulation(...).fit(n_rounds)`` with a
default ``Observability()`` — at the full width of a model the repo builds
(weights random from a seed, depth and round counts short):

1. device gate   platform must be ``tpu`` and ``device_specs`` must know the
                 chip; there is no retry on the CPU
2. encoder       12L d768 bf16 encoder, 4 clients: ``fit(3)`` pipelined and
                 auto (must pick chunked_scan); losses finite and falling,
                 the two modes agree (and agree tightly in f32 at narrow
                 width), state f32 and on the device, build-time
                 introspection reports and the manifest present
3. long_context  4L d512 seq-2048 through the Pallas flash kernel under
                 remat: the lowered round program holds the Mosaic custom
                 call (the kernel did not interpret)
4. kernels       flash fwd/bwd, the fused DP clip and the chunked
                 scalar-decay scan's two calls against their plain XLA
                 forms on the device (tools/tpu_selftest.py, in-process);
                 and the flash calls' microseconds an executed tile at the
                 trinity_mini cell's shape, not causal / causal / under the
                 window (``flash_tile_probe_*``: a reading, not a limit);
                 and how the routed layer's held rows travel at that cell's
                 shape: device microseconds a held row of its forward and
                 backward, whole and without the products, of one long
                 gather and one long scatter-add (``routed_rows_probe_*``),
                 and its combine (``kernels/row_combine.py``) against
                 XLA's scatter-add to the bit
5. cnn           CIFAR CNN, 64 clients, bf16: vmapped conv + donated stack
6. mesh_*        only with >= 4 devices, under ``MeshConfig``: the encoder
                 (one client per chip, against the one-chip trajectory), the
                 CNN (im2col conv), the flash config (Pallas inside a
                 clients-sharded program) and a ZeRO-1 ``fed_adam`` encoder

Any failed check, any exception, any stage skipped for a reason other than
the device count -> non-zero exit and no result line. On success the LAST
line of stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. Compile and wall seconds are printed as set-up facts; this
script measures no speed.

``--rehearsal`` runs the same stages at toy size on whatever backend is
there (CPU: interpret-mode kernels) to check this file's own control flow.
It is only ever asked for explicitly, says "rehearsal" in its output and
prints no result line. ``--stages a,b`` runs a subset (also no result line).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
MESH_MIN_DEVICES = 4
# Agreement of two runs that compute the same thing with differently built
# programs (pipelined vs chunked_scan; one chip vs the 4-chip mesh):
#
# - f32: the tolerance the CPU tests use (tests/server/test_chunked_fit.py
#   rtol 1e-5, test_mesh_fit.py atol/rtol 1e-5). On the chip this is checked
#   on the NARROW f32 encoder, where it holds.
# - bf16: XLA keeps excess precision inside fusions, the programs
#   fuse differently, so they round differently from the first step and
#   training amplifies it. Held to 1e-3 in the first round and 1e-1 after —
#   a check that the same job ran, not a regression gate.
# SGD step for the transformer configs. bench.py's 0.05 is past the edge of
# stability at full width: the 12-layer encoder starts at a loss near 11, and
# at 0.05 the f32 trajectory itself thrashes (XLA:CPU, eval loss 9.8 -> 15.6
# -> 3.6 over three rounds) — on the chip the two execution modes then
# disagreed by 2% and the mesh run by 25% in round 3, every one of them
# "right". At 0.005 the same job falls smoothly (f32 fit loss 3.9 -> 2.1 ->
# 1.1), so runs that should agree can be held to it.
TRANSFORMER_LR = 0.005
F32_RTOL = 1e-5
F32_MESH_RTOL = 1e-4  # the loosest rtol test_mesh_fit.py uses
BF16_RTOL_FIRST = 1e-3
BF16_RTOL_LATER = 1e-1


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one run. FULL is the width the repo's configs use; TOY keeps
    every code path and shrinks every axis for a rehearsal."""

    # encoder (bench.py "transformer")
    enc_vocab: int = 16384
    enc_d: int = 768
    enc_heads: int = 12
    enc_layers: int = 12
    enc_dff: int = 3072
    enc_seq: int = 128
    enc_clients: int = 4
    # long context (bench.py "transformer_long")
    long_vocab: int = 8192
    long_d: int = 512
    long_heads: int = 8
    long_layers: int = 4
    long_dff: int = 2048
    long_seq: int = 2048
    long_block: int = 128
    long_clients: int = 2
    # CIFAR CNN (bench.py "cifar_cnn")
    cnn_clients: int = 64
    cnn_hw: int = 32
    # shared
    batch: int = 32
    local_steps: int = 5
    toy: bool = False


FULL = Sizes()
# the f32 mode/mesh agreement check: 2 layers of d128, real batch and steps
NARROW = Sizes(enc_vocab=1024, enc_d=128, enc_heads=4, enc_layers=2,
               enc_dff=256, enc_seq=64)
TOY = Sizes(
    enc_vocab=64, enc_d=32, enc_heads=2, enc_layers=2, enc_dff=64, enc_seq=16,
    long_vocab=64, long_d=32, long_heads=2, long_layers=1, long_dff=64,
    long_seq=32, long_block=16,
    cnn_clients=8, cnn_hw=8, batch=4, local_steps=2, toy=True,
)


# ---------------------------------------------------------------------------
# Stage 1: device gate
# ---------------------------------------------------------------------------

def device_gate(rehearsal: bool) -> dict:
    """Print what JAX found; exit non-zero unless it is a TPU the spec table
    knows. A rehearsal (explicit flag only) accepts any backend."""
    import importlib.metadata

    import jax
    import jaxlib

    from fl4health_tpu.observability import device_specs

    devices = jax.devices()
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if rehearsal:
        print("REHEARSAL: toy sizes, no chip result — never a device metric",
              flush=True)
        return device
    if d.platform != "tpu":
        print(f"CHIP_SMOKE FAILED: device gate — platform is {d.platform!r}, "
              "not 'tpu' (a CPU rehearsal must be asked for with --rehearsal)",
              flush=True)
        raise SystemExit(2)
    if device_specs.lookup(d.device_kind) is None:
        print(f"CHIP_SMOKE FAILED: device gate — device_kind "
              f"{d.device_kind!r} is not in observability/device_specs.py",
              flush=True)
        raise SystemExit(2)
    return device


# ---------------------------------------------------------------------------
# Builders (the configs bench.py times, built here so the smoke stands alone)
# ---------------------------------------------------------------------------

def _text_datasets(sz: Sizes, n_clients, vocab, seq, n_val):
    import jax

    from fl4health_tpu.datasets.synthetic import synthetic_text_classification
    from fl4health_tpu.server.simulation import ClientDataset

    n = sz.batch * sz.local_steps
    out = []
    for i in range(n_clients):
        x, y = synthetic_text_classification(
            jax.random.PRNGKey(i), n + n_val, vocab, seq, 4)
        out.append(ClientDataset(x[:n], y[:n], x[n:], y[n:]))
    return out


def _make_sim(module, datasets, sz: Sizes, *, lr, execution_mode="auto",
              mesh=None, strategy=None):
    """The user-facing construction: default Observability(), FedAvg/SGD."""
    import optax

    from fl4health_tpu.clients import engine
    from fl4health_tpu.metrics import efficient
    from fl4health_tpu.metrics.base import MetricManager
    from fl4health_tpu.observability import Observability
    from fl4health_tpu.server.simulation import FederatedSimulation
    from fl4health_tpu.strategies.fedavg import FedAvg

    return FederatedSimulation(
        logic=engine.ClientLogic(engine.from_flax(module),
                                 engine.masked_cross_entropy),
        tx=optax.sgd(lr),
        strategy=strategy if strategy is not None else FedAvg(),
        datasets=datasets,
        batch_size=sz.batch,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=sz.local_steps,
        seed=0,
        execution_mode=execution_mode,
        mesh=mesh,
        observability=Observability(),
    )


def encoder_sim(sz: Sizes, dtype=None, **kw):
    import jax.numpy as jnp

    from fl4health_tpu.models.transformer import TransformerClassifier

    module = TransformerClassifier(
        vocab_size=sz.enc_vocab, n_classes=4, d_model=sz.enc_d,
        n_heads=sz.enc_heads, n_layers=sz.enc_layers, d_ff=sz.enc_dff,
        max_len=sz.enc_seq, dtype=dtype or jnp.bfloat16,
    )
    data = _text_datasets(sz, sz.enc_clients, sz.enc_vocab, sz.enc_seq, 32)
    return _make_sim(module, data, sz, lr=TRANSFORMER_LR, **kw)


def long_context_sim(sz: Sizes, n_clients: int, **kw):
    import jax.numpy as jnp

    from fl4health_tpu.kernels.flash_attention import flash_attention
    from fl4health_tpu.models.transformer import TransformerClassifier

    module = TransformerClassifier(
        vocab_size=sz.long_vocab, n_classes=4, d_model=sz.long_d,
        n_heads=sz.long_heads, n_layers=sz.long_layers, d_ff=sz.long_dff,
        max_len=sz.long_seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_attention, block_q=sz.long_block, block_k=sz.long_block),
    )
    data = _text_datasets(sz, n_clients, sz.long_vocab, sz.long_seq, 16)
    return _make_sim(module, data, sz, lr=TRANSFORMER_LR, **kw)


def cnn_sim(sz: Sizes, **kw):
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.datasets.synthetic import synthetic_classification
    from fl4health_tpu.models.cnn import CifarNet, resolve_conv_impl
    from fl4health_tpu.server.simulation import ClientDataset

    conv_impl = resolve_conv_impl(
        "auto", sharded_clients=kw.get("mesh") is not None)
    module = CifarNet(dtype=jnp.bfloat16, conv_impl=conv_impl)
    n = sz.batch * sz.local_steps
    data = []
    for i in range(sz.cnn_clients):
        x, y = synthetic_classification(
            jax.random.PRNGKey(i), n + 64, (sz.cnn_hw, sz.cnn_hw, 3), 10)
        data.append(ClientDataset(x[:n], y[:n], x[n:], y[n:]))
    return _make_sim(module, data, sz, lr=0.05, **kw), conv_impl


# ---------------------------------------------------------------------------
# Checks shared by the stages
# ---------------------------------------------------------------------------

def run_fit(sim, n_rounds: int, platform: str, expect_mode: str | None = None,
            expect_falling: bool = True) -> dict:
    """``sim.fit(n_rounds)`` plus everything a phase that broke could hide
    behind exit 0: finite losses, f32 masters, state on the device, the
    build-time introspection reports and the run manifest (fit() itself only
    logs when those two fail — here that is a failure)."""
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.server import simulation as simmod

    history = sim.fit(n_rounds)
    mode = sim._active_execution_mode
    fit_l = [float(r.fit_losses["backward"]) for r in history]
    eval_l = [float(r.eval_losses["checkpoint"]) for r in history]
    require(len(history) == n_rounds,
            f"fit({n_rounds}) returned {len(history)} round records")
    require(all(math.isfinite(v) for v in fit_l + eval_l),
            f"non-finite loss: fit={fit_l} eval={eval_l}")
    if expect_falling:
        require(fit_l[-1] < fit_l[0],
                f"fit loss did not fall over {n_rounds} rounds: {fit_l}")
    if expect_mode is not None:
        require(mode == expect_mode,
                f"execution mode {mode!r}, expected {expect_mode!r}")
    for leaf in jax.tree_util.tree_leaves(sim.client_states.params):
        require(leaf.dtype == jnp.float32,
                f"client master params are {leaf.dtype}, not float32")
    for leaf in jax.tree_util.tree_leaves(
            (sim.client_states, sim.server_state)):
        if isinstance(leaf, jax.Array):
            platforms = {d.platform for d in leaf.devices()}
            require(platforms == {platform},
                    f"state leaf {leaf.shape} lives on {platforms}, "
                    f"not {platform!r}")
    obs = sim.observability
    programs = (("fit_chunk_eval",) if mode == simmod.EXEC_CHUNKED
                else ("fit_round_t", "eval_round_t"))
    for name in programs:
        rep = obs.introspector.reports.get(name)
        require(rep is not None,
                f"no introspection report for the dispatched program "
                f"{name!r} (have {sorted(obs.introspector.reports)})")
        require(rep.flops is not None and rep.peak_hbm_bytes is not None,
                f"introspection of {name!r} lacks flops/peak_hbm_bytes: "
                f"{rep.flops}/{rep.peak_hbm_bytes}")
    mani = obs.manifest
    require(mani.get("device_kind") is not None
            and mani.get("execution_mode") == mode,
            f"run manifest missing or stale: {sorted(mani)}")
    return {"mode": mode, "fit_losses": fit_l, "eval_losses": eval_l,
            "programs": {n: (obs.introspector.reports[n].flops,
                             obs.introspector.reports[n].peak_hbm_bytes)
                         for n in programs}}


def _release(sim) -> None:
    """Drop a finished simulation's device state before the next one is
    built (two 12-layer client stacks need not coexist in HBM)."""
    sim.client_states = sim.server_state = None
    gc.collect()


def require_mosaic_call(sim, platform: str) -> bool:
    """The lowered round program holds the Mosaic custom call exactly when
    the backend is the TPU: interpret mode (a CPU rehearsal) lowers the
    kernel to plain HLO, and on the TPU its absence would mean the kernel
    did not compile via Mosaic."""
    mosaic = "tpu_custom_call" in lowered_round_text(sim)
    require(mosaic == (platform == "tpu"),
            f"Mosaic custom call present={mosaic} on {platform!r}")
    return mosaic


def lowered_round_text(sim) -> str:
    """StableHLO text of the round program ``fit()`` dispatched on the
    per-round path — for inspection only; the run itself went through
    ``fit()``."""
    import jax.numpy as jnp

    fit_fn = sim._fit_round_t if sim._telemetry_enabled else sim._fit_round
    return fit_fn.lower(
        sim.server_state, sim.client_states, sim._round_batches(1),
        sim.client_manager.sample_all(), jnp.asarray(1, jnp.int32),
        sim._val_batches()[0],
    ).as_text()


def _rtols(bf16: bool, n_rounds: int) -> list[float]:
    """Per-round tolerances of a same-device mode comparison (top of file)."""
    if bf16:
        return [BF16_RTOL_FIRST] + [BF16_RTOL_LATER] * (n_rounds - 1)
    return [F32_RTOL] * n_rounds


def require_same_trajectory(got: list[float], ref: list[float],
                            rtols: list[float], what: str) -> float:
    """Per-round relative agreement; returns the worst relative difference."""
    require(len(got) == len(ref), f"{what}: {len(got)} vs {len(ref)} rounds")
    rel = [abs(g - r) / max(abs(r), 1e-12) for g, r in zip(got, ref)]
    require(all(d <= t for d, t in zip(rel, rtols)),
            f"{what}: {got} vs {ref} (relative diffs "
            f"{[f'{d:.1e}' for d in rel]}, tolerances {rtols})")
    return max(rel)


# ---------------------------------------------------------------------------
# Stages 2-6
# ---------------------------------------------------------------------------

def _fit_both_modes(make_sim, platform: str, check=None) -> dict:
    """fit(3) under "pipelined" and under "auto" (which must pick the
    chunked scan); returns ``{mode: run_fit facts}``. ``check(sim)`` runs on
    each finished simulation before its state is dropped."""
    from fl4health_tpu.server import simulation as simmod

    facts = {}
    for mode, expect in (("pipelined", simmod.EXEC_PIPELINED),
                         ("auto", simmod.EXEC_CHUNKED)):
        sim = make_sim(execution_mode=mode)
        facts[mode] = run_fit(sim, 3, platform, expect_mode=expect)
        if check is not None:
            check(sim)
        _release(sim)
    return facts


def _require_modes_agree(facts: dict, bf16: bool, what: str) -> float:
    return max(
        require_same_trajectory(
            facts["auto"][key], facts["pipelined"][key],
            _rtols(bf16, len(facts["pipelined"][key])),
            what=f"{what}: chunked vs pipelined {key}")
        for key in ("fit_losses", "eval_losses"))


def stage_encoder(sz: Sizes, ctx: dict) -> str:
    import jax.numpy as jnp

    facts = _fit_both_modes(functools.partial(encoder_sim, sz),
                            ctx["platform"])
    ctx["encoder_fit_losses"] = facts["pipelined"]["fit_losses"]
    worst = _require_modes_agree(facts, bf16=True, what="encoder")
    out = (f"fit_losses={facts['pipelined']['fit_losses']} "
           f"chunked={facts['auto']['fit_losses']} "
           f"modes_max_rel_diff={worst:.1e} "
           f"programs={facts['pipelined']['programs']} "
           f"{facts['auto']['programs']}")
    if not sz.toy:
        # the same two programs in f32 at narrow width: here the modes must
        # agree to the CPU tests' tolerance (a rehearsal's encoder is
        # already narrow and exact, so it has nothing to add there)
        narrow = _fit_both_modes(
            functools.partial(encoder_sim, NARROW, dtype=jnp.float32),
            ctx["platform"])
        worst32 = _require_modes_agree(narrow, bf16=False,
                                       what="narrow f32 encoder")
        ctx["narrow_f32_fit_losses"] = narrow["pipelined"]["fit_losses"]
        out += (f" narrow_f32_fit_losses={narrow['pipelined']['fit_losses']}"
                f" narrow_f32_modes_max_rel_diff={worst32:.1e}")
    return out


def stage_long_context(sz: Sizes, ctx: dict) -> str:
    sim = long_context_sim(sz, sz.long_clients, execution_mode="pipelined")
    facts = run_fit(sim, 2, ctx["platform"], expect_falling=False)
    mosaic = require_mosaic_call(sim, ctx["platform"])
    _release(sim)
    return (f"fit_losses={facts['fit_losses']} seq={sz.long_seq} "
            f"block={sz.long_block} tpu_custom_call={mosaic}")


def stage_kernels(sz: Sizes, ctx: dict) -> str:
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tpu_selftest

    checks = tpu_selftest.run_checks(toy=sz.toy)
    for c in checks:
        print(f"  kernel check {c['name']}: {'ok' if c['ok'] else 'FAILED'} "
              f"— {c['detail']}", flush=True)
    failed = [c["name"] for c in checks if not c["ok"]]
    require(not failed, f"kernel checks failed: {failed}")
    return f"{len(checks)} checks"


def stage_cnn(sz: Sizes, ctx: dict) -> str:
    sim, conv_impl = cnn_sim(sz)
    facts = run_fit(sim, 3, ctx["platform"])
    _release(sim)
    return (f"fit_losses={facts['fit_losses']} clients={sz.cnn_clients} "
            f"conv_impl={conv_impl} mode={facts['mode']}")


def _require_clients_sharded(sim, n_devices: int, platform: str) -> None:
    """Every [C, ...] leaf of the client stack is split over the clients axis
    across ``n_devices`` DISTINCT devices; the server state is replicated
    (or, under ZeRO-1, sharded by the strategy's own spec)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(sim.client_states):
        spec = tuple(leaf.sharding.spec)
        require(spec[:1] == ("clients",) and not any(spec[1:]),
                f"client leaf {leaf.shape} has spec {leaf.sharding.spec}")
        devs = {s.device for s in leaf.addressable_shards}
        require(len(devs) == n_devices
                and {d.platform for d in devs} == {platform},
                f"client leaf {leaf.shape} sits on {len(devs)} device(s): "
                f"{sorted(str(d) for d in devs)}")


def _four_chip_mesh():
    """clients axis = 4: one encoder client per chip."""
    from fl4health_tpu.parallel.program import MeshConfig

    return MeshConfig(clients=MESH_MIN_DEVICES)


def stage_mesh_encoder(sz: Sizes, ctx: dict) -> str:
    import jax
    import jax.numpy as jnp

    platform = ctx["platform"]
    base = ctx.get("encoder_fit_losses")
    require(base is not None,
            "compares against the one-chip encoder stage — run it in the "
            "same process (--stages encoder,mesh_encoder)")

    def placed(sim):
        _require_clients_sharded(sim, MESH_MIN_DEVICES, platform)
        for leaf in jax.tree_util.tree_leaves(sim.server_state):
            require(leaf.sharding.is_fully_replicated,
                    f"server leaf {leaf.shape} is not replicated: "
                    f"{leaf.sharding}")

    facts = _fit_both_modes(
        functools.partial(encoder_sim, sz, mesh=_four_chip_mesh()), platform,
        check=placed)
    out = []
    for mode, f in facts.items():
        print(f"  mesh encoder {mode}: fit_losses={f['fit_losses']} "
              f"(one chip {base})", flush=True)
        worst = require_same_trajectory(
            f["fit_losses"], base, _rtols(True, len(base)),
            what=f"mesh {mode} vs one-chip encoder fit losses")
        out.append(f"{mode} max_rel_diff_vs_one_chip={worst:.1e}")
    if "narrow_f32_fit_losses" in ctx:
        # f32: sharding only reorders the cross-client reductions, so the
        # CPU mesh tests' tolerance carries over to the chip
        ref = ctx["narrow_f32_fit_losses"]
        sim = encoder_sim(NARROW, dtype=jnp.float32,
                          execution_mode="pipelined", mesh=_four_chip_mesh())
        facts = run_fit(sim, 3, platform)
        _release(sim)
        print(f"  mesh narrow f32: fit_losses={facts['fit_losses']} "
              f"(one chip {ref})", flush=True)
        worst = require_same_trajectory(
            facts["fit_losses"], ref, [F32_MESH_RTOL] * len(ref),
            what="mesh vs one-chip narrow f32 encoder fit losses")
        out.append(f"narrow_f32 max_rel_diff_vs_one_chip={worst:.1e}")
    return "; ".join(out)


def stage_mesh_cnn(sz: Sizes, ctx: dict) -> str:
    import jax

    from fl4health_tpu.parallel.program import MeshConfig

    n_dev = len(jax.devices())
    sim, conv_impl = cnn_sim(sz, mesh=MeshConfig())
    facts = run_fit(sim, 3, ctx["platform"])
    _require_clients_sharded(sim, n_dev, ctx["platform"])
    _release(sim)
    return (f"fit_losses={facts['fit_losses']} conv_impl={conv_impl} "
            f"{sz.cnn_clients} clients over {n_dev} devices")


def stage_mesh_flash(sz: Sizes, ctx: dict) -> str:
    """The Pallas call inside a clients-sharded program."""
    sim = long_context_sim(sz, MESH_MIN_DEVICES,
                           execution_mode="pipelined", mesh=_four_chip_mesh())
    facts = run_fit(sim, 2, ctx["platform"], expect_falling=False)
    _require_clients_sharded(sim, MESH_MIN_DEVICES, ctx["platform"])
    mosaic = require_mosaic_call(sim, ctx["platform"])
    _release(sim)
    return (f"fit_losses={facts['fit_losses']} "
            f"clients={MESH_MIN_DEVICES} tpu_custom_call={mosaic}")


def stage_mesh_zero1(sz: Sizes, ctx: dict) -> str:
    """ZeRO-1: ``parallel/zero.py``'s shard_map update and its
    construction-time parity probe on the live mesh."""
    from fl4health_tpu.parallel.program import MeshConfig
    from fl4health_tpu.strategies.fedopt import fed_adam

    sim = encoder_sim(
        sz, mesh=MeshConfig(clients=MESH_MIN_DEVICES, zero1=True),
        strategy=fed_adam(lr=1e-3))
    facts = run_fit(sim, 3, ctx["platform"], expect_falling=False)
    _require_clients_sharded(sim, MESH_MIN_DEVICES, ctx["platform"])
    _release(sim)
    return f"fed_adam fit_losses={facts['fit_losses']}"


STAGE_FNS = {
    "encoder": stage_encoder,
    "long_context": stage_long_context,
    "kernels": stage_kernels,
    "cnn": stage_cnn,
    "mesh_encoder": stage_mesh_encoder,
    "mesh_cnn": stage_mesh_cnn,
    "mesh_flash": stage_mesh_flash,
    "mesh_zero1": stage_mesh_zero1,
}
STAGES = tuple(STAGE_FNS)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_stage(name: str, sz: Sizes, ctx: dict, counters) -> bool:
    """Run one stage, print its line (compile and wall seconds are set-up
    facts, not metrics); returns whether it passed."""
    c0, h0 = counters()
    t0 = time.perf_counter()
    try:
        detail = STAGE_FNS[name](sz, ctx)
        status = "OK"
    except Exception as e:  # noqa: BLE001 — a stage failure is reported, then fails the run
        traceback.print_exc()
        msg = str(e)
        detail = f"{type(e).__name__}: {msg[:600]}"
        status = "FAILED"
    c1, h1 = counters()
    print(f"stage {name}: {status} compile_s={c1 - c0:.1f} "
          f"wall_s={time.perf_counter() - t0:.1f} "
          f"persistent_cache_hits={int(h1 - h0)} — {detail}", flush=True)
    return status == "OK"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rehearsal", action="store_true",
                        help="toy sizes on any backend; prints no result")
    parser.add_argument("--stages", default=",".join(STAGES),
                        help=f"comma list out of {','.join(STAGES)}")
    args = parser.parse_args(argv)
    stages = [s for s in args.stages.split(",") if s]
    unknown = [s for s in stages if s not in STAGES]
    if unknown:
        parser.error(f"unknown stage(s) {unknown}; choose from {STAGES}")

    from fl4health_tpu.utils.runtime import configure_compile_cache

    cache_dir = configure_compile_cache()
    device = device_gate(args.rehearsal)
    print(f"compile cache: {cache_dir}", flush=True)

    # compile accounting for the stage lines: a private registry, so the
    # per-run Observability handles (global registry) are not double-fed
    from fl4health_tpu.observability.jaxmon import CompileMonitor
    from fl4health_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    monitor = CompileMonitor(reg).install()

    def counters():
        return (reg.counter("jax_backend_compiles_seconds_total").value,
                reg.counter("jax_persistent_cache_hits_total").value)

    sz = TOY if args.rehearsal else FULL
    ctx = {"platform": device["platform"]}
    failed = []
    try:
        for name in stages:
            if name.startswith("mesh_") and device["count"] < MESH_MIN_DEVICES:
                continue
            if not run_stage(name, sz, ctx, counters):
                failed.append(name)
        if device["count"] < MESH_MIN_DEVICES:
            print(f"mesh stage: not run ({device['count']} device)",
                  flush=True)
    finally:
        monitor.uninstall()
    print(f"jax_persistent_cache_hits_total={int(counters()[1])} "
          f"backend_compile_s_total={counters()[0]:.1f}", flush=True)

    if failed:
        print(f"CHIP_SMOKE FAILED: {', '.join(failed)}", flush=True)
        return 1
    if args.rehearsal or stages != list(STAGES):
        print(f"CHIP_SMOKE {'REHEARSAL' if args.rehearsal else 'PARTIAL'} OK "
              f"(stages: {','.join(stages)}) — not a chip result", flush=True)
        return 0
    print("CHIP_SMOKE OK", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
