"""Benchmark: FedAvg on a CIFAR-10-class CNN with 64 simulated clients, plus
a BERT-class transformer config — with achieved TFLOP/s and %MFU.

Prints ONE JSON line. Primary fields {"metric", "value", "unit",
"vs_baseline"} report compiled local-steps/sec/chip for the CIFAR config and
the eager-dispatch speedup; provenance and speed facts ride along:
  platform/device_kind  — which backend actually ran (a CPU fallback can
                          never masquerade as the TPU number),
  tflops/mfu_pct        — achieved TFLOP/s and the fraction of the chip's
                          bf16 peak; tflops_measured (XLA compiled cost
                          analysis) vs tflops_analytic (formula count) are
                          reported separately, and every one of these is
                          null — never 0.0 — when no measured or applicable
                          analytic number exists for the backend,
  program_introspection — the compiled fit_round's cost/memory analysis
                          (flops, bytes accessed, HBM footprint, compile
                          wall) plus hbm_headroom_bytes where capacity is
                          known,
  dtype                 — compute dtype (bf16 on TPU, fp32 on CPU fallback),
  transformer           — the same measurements for the transformer config.

``vs_baseline`` compares against a reference-style eager simulation measured
on the SAME hardware: a Python loop over clients, each running eager
(un-jitted) train steps with host round-trips per step and per-round
parameter serialization — the dispatch pattern of the reference's
Flower/PyTorch stack (SURVEY.md §3.1-3.2). That ratio is a PROXY for the
10x-vs-A100-Flower north star in BASELINE.json (eager JAX dispatch is not an
A100 Flower stack); the MFU figure is the absolute-speed evidence.

Process model: each config is measured in its own child process and the
parent never initialises a JAX backend (a chip belongs to one process at a
time). The measurement requires a TPU: a child whose default backend is
anything else exits non-zero, and so does the parent — there is no automatic
retry on the CPU. FL4HEALTH_BENCH_FORCE_CPU=1 is the one explicit switch that
runs the harness on XLA:CPU (used by the smoke test); its record is labelled
``_cpu_fallback`` and certifies the harness, not a speed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# Env overrides let the CPU smoke test (tests/server/test_driver_entry.py) run
# the exact same code path with a tiny config.
N_CLIENTS = int(os.environ.get("FL4HEALTH_BENCH_CLIENTS", 64))
BATCH = int(os.environ.get("FL4HEALTH_BENCH_BATCH", 32))
LOCAL_STEPS = int(os.environ.get("FL4HEALTH_BENCH_STEPS", 5))
TIMED_ROUNDS = int(os.environ.get("FL4HEALTH_BENCH_ROUNDS", 3))
CHILD_TIMEOUT_S = int(os.environ.get("FL4HEALTH_BENCH_TIMEOUT_S", 1500))

# Published bf16 peak matmul throughput per chip lives in the shared spec
# table (observability/device_specs.py — also the MFU denominator for the
# per-round measured numbers fit() now records). Unknown kinds report no MFU.
from fl4health_tpu.observability import device_specs  # noqa: E402 (no jax at import)

# FLOP-based bridge to the north star (BASELINE.json: >=10x vs single-A100
# Flower simulation). The A100 run cannot exist in this environment, so the
# bridge MODELS it: the per-round FLOPs are identical (same model/config),
# so speedup = (measured TPU TFLOP/s) / (A100 peak x Flower utilization).
# The utilization band is DERIVED from a measured chain (tools/
# a100_band_anchor.py -> A100_BAND_ANCHOR.json; derivation in BASELINE.md):
# the measured ~1.1 ms/step eager dispatch overhead against A100 spec peaks
# bounds eager small-CNN utilization to 0.9-5.0%; the low end is rounded UP
# to 1% so the modeled speedup band's high end under-claims.
A100_PEAK_BF16_FLOPS = 312e12
FLOWER_A100_UTIL_BAND = (0.01, 0.05)


def modeled_vs_a100_flower(achieved_flops: float) -> dict | None:
    """Model-based bridge, not a measurement — returns the modeled speedup
    band; the utilization band is derived from the measured chain in
    A100_BAND_ANCHOR.json (see BASELINE.md)."""
    if not achieved_flops:
        return None
    lo_util, hi_util = FLOWER_A100_UTIL_BAND
    return {
        # generous-to-baseline utilization -> LOW end of our speedup
        "low": round(achieved_flops / (hi_util * A100_PEAK_BF16_FLOPS), 2),
        "high": round(achieved_flops / (lo_util * A100_PEAK_BF16_FLOPS), 2),
        "model": (
            "measured TFLOP/s / (A100 312 TFLOP/s bf16 x Flower "
            f"utilization {lo_util:.0%}-{hi_util:.0%}, band derived from "
            "the measured chain in A100_BAND_ANCHOR.json); FLOP-parity "
            "bridge (same model+config), NOT an A100 measurement"
        ),
    }


def flash_requested(default: bool) -> bool:
    """One semantics for FL4HEALTH_BENCH_FLASH across configs AND artifact
    labels: '1'/'true' forces the Pallas kernel, '0'/'false' forces dense,
    unset/other -> the config's default."""
    v = os.environ.get("FL4HEALTH_BENCH_FLASH", "").lower()
    if v in ("1", "true"):
        return True
    if v in ("0", "false"):
        return False
    return default


def _provenance() -> tuple[str, str]:
    import jax

    d = jax.devices()[0]
    return d.platform, getattr(d, "device_kind", "unknown")


def _git_rev() -> str | None:
    """Current commit (+'-dirty' when the tree has changes); None outside
    a git checkout — absence, never a placeholder a diff could match."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        return rev + ("-dirty" if dirty else "")
    except Exception:
        return None


def provenance_block() -> dict:
    """The ``provenance`` block every bench artifact carries so a
    CPU-fallback number can never masquerade as a TPU capture: backend +
    device kind, jax/jaxlib versions, git rev, and the explicit
    ``cpu_fallback`` flag ``tools/bench_gate.py`` cross-checks against the
    artifact's metric name."""
    import jax
    import jaxlib

    platform, device_kind = _provenance()
    return {
        "backend": platform,
        "device_kind": device_kind,
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "git_rev": _git_rev(),
        "cpu_fallback": platform == "cpu",
    }


def _bench_dtype():
    """bf16 on the TPU (the MXU-native path), fp32 on CPU (bf16 is emulated
    there), an error anywhere else; FL4HEALTH_BENCH_DTYPE=float32|bfloat16
    overrides."""
    import jax.numpy as jnp

    forced = os.environ.get("FL4HEALTH_BENCH_DTYPE")
    if forced:
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[forced]
    platform, _ = _provenance()
    if platform == "tpu":
        return jnp.bfloat16
    if platform == "cpu":
        return jnp.float32
    raise RuntimeError(
        f"bench: no dtype policy for backend {platform!r} (tpu -> bfloat16, "
        "cpu -> float32); set FL4HEALTH_BENCH_DTYPE to run it anyway"
    )


def analytic_transformer_round_flops(
    d: int, d_ff: int, n_layers: int, seq: int, n_clients: int
) -> float:
    """Model FLOPs per fit round, standard 3x-forward convention (1 fwd +
    2 bwd; remat recompute NOT counted — useful work, PaLM-style MFU).

    Needed because XLA's cost_analysis cannot see inside a Pallas custom
    call: with flash attention the whole T^2 score work vanishes from the
    cost model and the reported MFU undercounts ~7x at seq 2048 (measured
    r5: 1.29% cost-model vs 8.8% analytic on the same run). Per token per
    layer forward: 8d^2 (QKV+O) + 4Td (QK^T + PV) + 4*d*d_ff (MLP);
    embedding gather and the tiny classifier head are ignored.

    Thin wrapper over the single shared numerator rule in
    ``fl4health_tpu/observability/flops.py`` — the same convention
    ``tools/flash_crossover.py`` uses, so no two tools can disagree about
    the same model.
    """
    from fl4health_tpu.observability import flops as flops_rules

    return flops_rules.transformer_round_flops(
        d, d_ff, n_layers, seq, n_clients, batch=BATCH,
        local_steps=LOCAL_STEPS,
    )


def _headline_conv_impl() -> str:
    """The resolved conv impl of the (unsharded) headline config — what the
    artifact's ``conv_impl`` field must name (the env may say "auto")."""
    from fl4health_tpu.models.cnn import resolve_conv_impl

    return resolve_conv_impl(os.environ.get("FL4HEALTH_BENCH_CONV", "auto"))


def make_sim(model_kind: str = "cifar_cnn", conv_impl: str | None = None,
             n_clients_override: int | None = None, mesh=None,
             observability=None, precision=None, model_dtype=None):
    """``conv_impl``/``n_clients_override``/``mesh``/``observability`` are
    overrides for the mesh block (timed_mesh_rounds) and the multichip
    artifact: a sharded clients axis requires the im2col MxuConv lowering
    (XLA's partitioner rejects the grouped-conv one) and a cohort divisible
    by the device count; observability must be present at construction so
    the round programs are built against it (post-construction assignment
    would leave the telemetry/introspection variants unbuilt).
    ``precision``/``model_dtype`` serve the precision block
    (timed_precision_block): the A/B pins the MODEL dtype to f32 so the
    engine-level PrecisionConfig is the only difference between arms."""
    import jax
    import optax

    from fl4health_tpu.clients import engine
    from fl4health_tpu.datasets.synthetic import (
        synthetic_classification,
        synthetic_text_classification,
    )
    from fl4health_tpu.metrics import efficient
    from fl4health_tpu.metrics.base import MetricManager
    from fl4health_tpu.models.cnn import CifarNet
    from fl4health_tpu.models.transformer import TransformerClassifier
    from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
    from fl4health_tpu.strategies.fedavg import FedAvg

    dtype = model_dtype if model_dtype is not None else _bench_dtype()
    datasets = []
    analytic_flops = None  # set where the XLA cost model undercounts

    def split_train_val(x, y):
        # shared train/val slicing for every config's ClientDataset
        n = BATCH * LOCAL_STEPS
        return ClientDataset(x_train=x[:n], y_train=y[:n],
                             x_val=x[n:], y_val=y[n:])

    if model_kind == "cifar_cnn":
        # Conv impl selection (models/cnn.py resolve_conv_impl): the
        # default "auto" resolves per backend/mesh — "lax" (grouped conv)
        # everywhere the partitioner accepts it (the real-TPU A/B in the
        # MxuConv docstring: grouped 3186 vs im2col 606 steps/s on a v5e),
        # "mxu" only under a clients-sharded mesh, where XLA's grouped-conv
        # partitioner rejects the vmapped nn.Conv outright. Pin with
        # FL4HEALTH_BENCH_CONV=lax|mxu and compare conv_impl fields.
        from fl4health_tpu.models.cnn import resolve_conv_impl

        if conv_impl is None:
            conv_impl = os.environ.get("FL4HEALTH_BENCH_CONV", "auto")
        conv_impl = resolve_conv_impl(
            conv_impl, sharded_clients=mesh is not None
        )
        module = CifarNet(dtype=dtype, conv_impl=conv_impl)
        n_clients = n_clients_override or N_CLIENTS
        for i in range(n_clients):
            x, y = synthetic_classification(
                jax.random.PRNGKey(i), BATCH * LOCAL_STEPS + 64, (32, 32, 3), 10
            )
            datasets.append(split_train_val(x, y))
    elif model_kind == "transformer_long":
        # Long-context config: the flash-attention Pallas kernel carries the
        # T² score memory (SURVEY: long-context is first-class). Only worth
        # timing on real TPU — interpret-mode Pallas on CPU is orders slower.
        import functools

        from fl4health_tpu.kernels.flash_attention import flash_attention

        seq = int(os.environ.get("FL4HEALTH_BENCH_LONGSEQ", 2048))
        module = TransformerClassifier(
            vocab_size=8192, n_classes=4, d_model=512, n_heads=8,
            n_layers=4, d_ff=2048, max_len=seq, dtype=dtype, remat=True,
            attention_fn=(
                functools.partial(flash_attention, block_q=128, block_k=128)
                if flash_requested(default=True) else None
            ),
        )
        for i in range(2):
            x, y = synthetic_text_classification(
                jax.random.PRNGKey(i), BATCH * LOCAL_STEPS + 16,
                module.vocab_size, seq, module.n_classes,
            )
            datasets.append(split_train_val(x, y))
        if flash_requested(default=True):
            analytic_flops = analytic_transformer_round_flops(
                d=module.d_model, d_ff=module.d_ff, n_layers=module.n_layers,
                seq=seq, n_clients=len(datasets),
            )
    else:  # transformer: the BERT-shaped AG-News config (SURVEY §6)
        seq = int(os.environ.get("FL4HEALTH_BENCH_SEQ", 128))
        attention_fn = None
        if flash_requested(default=False):
            import functools

            from fl4health_tpu.kernels.flash_attention import flash_attention

            attention_fn = functools.partial(flash_attention, block_q=128,
                                             block_k=128)
        module = TransformerClassifier(
            vocab_size=int(os.environ.get("FL4HEALTH_BENCH_VOCAB", 16384)),
            n_classes=4,
            d_model=int(os.environ.get("FL4HEALTH_BENCH_DMODEL", 768)),
            # heads scale with width so env overrides of d_model stay valid
            n_heads=int(
                os.environ.get(
                    "FL4HEALTH_BENCH_HEADS",
                    max(int(os.environ.get("FL4HEALTH_BENCH_DMODEL", 768)) // 64, 1),
                )
            ),
            n_layers=int(os.environ.get("FL4HEALTH_BENCH_LAYERS", 12)),
            d_ff=int(os.environ.get("FL4HEALTH_BENCH_DFF", 3072)),
            max_len=seq,
            dtype=dtype,
            attention_fn=attention_fn,
        )
        n_clients = int(os.environ.get("FL4HEALTH_BENCH_TRANSFORMER_CLIENTS", 4))
        for i in range(n_clients):
            x, y = synthetic_text_classification(
                jax.random.PRNGKey(i), BATCH * LOCAL_STEPS + 32,
                module.vocab_size, seq, 4,
            )
            datasets.append(split_train_val(x, y))
        # FLASH=1: cost_analysis would drop the Pallas attention FLOPs here
        # exactly as in transformer_long. FL4HEALTH_BENCH_ANALYTIC_FLOPS=1
        # (tools/flash_crossover.py sets it for BOTH arms) forces the same
        # analytic numerator on the dense arm too, so per-cell mfu_pct is
        # apples-to-apples across dense and flash.
        if (attention_fn is not None
                or os.environ.get("FL4HEALTH_BENCH_ANALYTIC_FLOPS") == "1"):
            analytic_flops = analytic_transformer_round_flops(
                d=module.d_model, d_ff=module.d_ff, n_layers=module.n_layers,
                seq=seq, n_clients=n_clients,
            )
    return analytic_flops, FederatedSimulation(
        logic=engine.ClientLogic(
            engine.from_flax(module), engine.masked_cross_entropy
        ),
        tx=optax.sgd(0.05),
        strategy=FedAvg(),
        datasets=datasets,
        batch_size=BATCH,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=LOCAL_STEPS,
        seed=0,
        mesh=mesh,
        observability=observability,
        precision=precision,
    )


def compile_fit_round(sim):
    """AOT-compile fit_round ONCE; return (compiled, ProgramReport).

    The compiled executable is reused for the timed rounds so the multi-
    minute XLA compile of the big configs is paid a single time; its XLA
    cost/memory analysis (observability/introspect.py) provides the MFU
    numerator plus the HBM footprint. Report fields are ``None`` (never a
    fake 0.0) where the backend exposes no analysis.
    """
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.observability.introspect import (
        ProgramReport,
        analyze_compiled,
    )

    mask = sim.client_manager.sample_all()
    batches = sim._round_batches(0)
    val_batches, _ = sim._val_batches()
    t0 = time.perf_counter()
    compiled = sim._fit_round.lower(
        sim.server_state, sim.client_states, batches, mask,
        jnp.asarray(1, jnp.int32), val_batches,
    ).compile()
    compile_s = time.perf_counter() - t0
    d = jax.devices()[0]
    report = ProgramReport(
        name="fit_round",
        backend=d.platform,
        device_kind=getattr(d, "device_kind", "unknown"),
        compile_seconds=compile_s,
        **analyze_compiled(compiled),
    )
    return compiled, report


def timed_chunked_rounds(sim) -> float:
    """Wall time per round of the on-device multi-round scan: ONE dispatch
    executes TIMED_ROUNDS rounds (simulation.make_chunked_fit — semantics
    pinned equal to the per-round path by tests/server/test_chunked_fit.py).
    This is the framework's real hot path: per-round dispatch latency is
    amortized away."""
    import jax

    # warmup dispatch compiles the scan and pages it in; BLOCK on it so the
    # timed chunk doesn't queue behind still-running async warmup work
    warm_losses, _ = sim.fit_chunk(start_round=1, k=TIMED_ROUNDS)
    jax.block_until_ready(warm_losses["backward"])
    t0 = time.perf_counter()
    losses, _ = sim.fit_chunk(start_round=1 + TIMED_ROUNDS, k=TIMED_ROUNDS)
    jax.block_until_ready(losses["backward"])
    return (time.perf_counter() - t0) / TIMED_ROUNDS


def timed_compiled_rounds(sim, compiled) -> float:
    """Wall time per round of the compiled fit path (excludes compile).

    The executable donates its state arguments (simulation.py mirrors
    fit_chunk's donate_argnums), so the warmup outputs — not the consumed
    sim fields — seed the timed loop, and the final states are written back
    so later measurements (chunked, eager) see live buffers."""
    import jax
    import jax.numpy as jnp

    mask = sim.client_manager.sample_all()
    val_batches, _ = sim._val_batches()
    r = jnp.asarray(1, jnp.int32)
    # warmup (executable already compiled; first call pages it in)
    server_state, client_states, *_ = compiled(
        sim.server_state, sim.client_states, sim._round_batches(0), mask, r,
        val_batches,
    )
    jax.block_until_ready(jax.tree_util.tree_leaves(server_state)[0])
    t0 = time.perf_counter()
    for i in range(TIMED_ROUNDS):
        # Honest full-round cost: per-round batch construction included
        # (host index plan + one device gather), exactly as fit() pays it.
        round_batches = sim._round_batches(i + 1)
        server_state, client_states, losses, metrics, _per_client = compiled(
            server_state, client_states, round_batches, mask, r, val_batches
        )
    jax.block_until_ready(jax.tree_util.tree_leaves(server_state)[0])
    per_round = (time.perf_counter() - t0) / TIMED_ROUNDS
    sim.server_state, sim.client_states = server_state, client_states
    return per_round


def timed_fit_overhead(sim) -> dict:
    """Host-overhead decomposition of the REAL fit() driver loop, tracked in
    BENCH_* from the async-pipeline PR onward.

    device_busy_s: fit+eval dispatches for TIMED_ROUNDS rounds with a single
    terminal block — what the devices are actually busy (plus per-round
    batch construction, exactly as fit() pays it).
    host_busy_s: fit() wall per round minus device_busy_s — the driver
    loop's own per-round cost (pipelined path: consumer/prefetch overlap).
    """
    import jax
    import jax.numpy as jnp

    mask = sim.client_manager.sample_all()
    val_batches, val_counts = sim._val_batches()
    r = jnp.asarray(1, jnp.int32)
    # device-only loop. Warm BOTH jits first: earlier measurements used the
    # AOT-compiled executable, so sim._fit_round's own jit (what fit()
    # dispatches) still needs its trace+compile paid outside the timing.
    ss, cs = sim.server_state, sim.client_states
    ss, cs, *_ = sim._fit_round(ss, cs, sim._round_batches(0), mask, r,
                                val_batches)
    ev = sim._eval_round(ss, cs, val_batches, val_counts)
    jax.block_until_ready(ev[1])
    cs = ev[0]
    t0 = time.perf_counter()
    for i in range(TIMED_ROUNDS):
        b = sim._round_batches(i + 1)
        ss, cs, *_ = sim._fit_round(ss, cs, b, mask, r, val_batches)
        ev = sim._eval_round(ss, cs, val_batches, val_counts)
        cs = ev[0]
    jax.block_until_ready((jax.tree_util.tree_leaves(ss)[0], ev[1]))
    device_busy = (time.perf_counter() - t0) / TIMED_ROUNDS
    sim.server_state, sim.client_states = ss, cs

    # the real driver loop on the pipelined path (the mode whose host
    # overhead this PR targets; chunked would hide it by construction)
    sim.execution_mode = "pipelined"
    sim.fit(1)  # warmup: everything fit() touches is compiled after this
    t0 = time.perf_counter()
    sim.fit(TIMED_ROUNDS)
    wall = (time.perf_counter() - t0) / TIMED_ROUNDS
    host_busy = max(0.0, wall - device_busy)
    return {
        "fit_wall_s": round(wall, 4),
        "device_busy_s": round(device_busy, 4),
        "host_busy_s": round(host_busy, 4),
        "host_device_ratio": (
            round(host_busy / device_busy, 4) if device_busy else None
        ),
        "fit_execution_mode": "pipelined_per_round",
        "rounds": TIMED_ROUNDS,
    }


def _timed_round_loop(sim, fit_fn) -> float:
    """Fenced per-round wall of ``fit_fn`` dispatch loops (one warmup
    dispatch, donation-safe state threading, TIMED_ROUNDS measured).
    Shared by the telemetry/resilience overhead blocks so the two numbers
    stay measured under identical discipline."""
    import jax
    import jax.numpy as jnp

    mask = sim.client_manager.sample_all()
    val_batches, _ = sim._val_batches()
    r = jnp.asarray(1, jnp.int32)
    ss, cs = sim.server_state, sim.client_states
    ss, cs, *rest = fit_fn(ss, cs, sim._round_batches(0), mask, r,
                           val_batches)
    jax.block_until_ready(rest[0])
    t0 = time.perf_counter()
    for i in range(TIMED_ROUNDS):
        b = sim._round_batches(i + 1)
        ss, cs, *rest = fit_fn(ss, cs, b, mask, r, val_batches)
    jax.block_until_ready((jax.tree_util.tree_leaves(ss)[0], rest[0]))
    per_round = (time.perf_counter() - t0) / TIMED_ROUNDS
    sim.server_state, sim.client_states = ss, cs
    return per_round


def timed_telemetry_overhead(sim) -> dict:
    """Device cost of the in-graph telemetry outputs (observability PR
    acceptance metric): per-round time of the compiled fit round WITHOUT
    telemetry vs WITH the RoundTelemetry extra outputs compiled in.

    Rebuilds the sim's round programs with an enabled (but artifact-less)
    Observability so the telemetry variant exists, times both dispatch
    loops fenced, and restores the original observability handle. The
    telemetry stats are derived from values the round already computes, so
    the expected overhead is a few extra reductions per round.
    """
    from fl4health_tpu.observability import (
        MetricsRegistry,
        Observability,
        Tracer,
    )

    plain_s = _timed_round_loop(sim, sim._fit_round)
    prev_obs = sim.observability
    # sync_device=False + no output_dir: the handle exists only to flip the
    # telemetry compile flag — no fences, no artifacts, no global state
    temp_obs = Observability(
        enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
        sync_device=False,
    )
    sim.observability = temp_obs
    try:
        sim._build_compiled()
        telemetry_s = _timed_round_loop(sim, sim._fit_round_t)
    finally:
        # shutdown detaches the temp handle's CompileMonitor from the
        # process-wide jax.monitoring fan-out (enabled __init__ installed it)
        temp_obs.shutdown()
        sim.observability = prev_obs
        sim._build_compiled()
    return {
        "round_s_plain": round(plain_s, 5),
        "round_s_telemetry": round(telemetry_s, 5),
        "overhead_pct": (
            round(100.0 * (telemetry_s - plain_s) / plain_s, 2)
            if plain_s > 0 else None
        ),
        "rounds": TIMED_ROUNDS,
    }


def timed_flightrec_overhead(sim) -> dict:
    """Host cost of the flight recorder (flight-recorder PR acceptance
    metric): per-round wall of the REAL ``fit()`` driver loop with the
    black-box ring disabled vs enabled (the default). The recorder only
    copies host data the round epilogue already pulled off-device, so the
    expected overhead is noise-level — this block exists to prove that on
    real accelerators, the same way ``telemetry_overhead`` proves the
    in-graph half."""
    from fl4health_tpu.observability import (
        MetricsRegistry,
        Observability,
        Tracer,
    )

    prev_obs = sim.observability
    prev_mode = sim.execution_mode
    # pipelined: the mode whose consumer-thread epilogue hosts the
    # recorder feed (the chunked scan would amortize it invisibly)
    sim.execution_mode = "pipelined"

    def arm(flight: bool) -> float:
        obs = Observability(
            enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
            sync_device=False, flight_recorder=flight,
        )
        sim.observability = obs
        try:
            sim._build_compiled()
            sim.fit(1)  # warmup: every program fit() touches is compiled
            t0 = time.perf_counter()
            sim.fit(TIMED_ROUNDS)
            return (time.perf_counter() - t0) / TIMED_ROUNDS
        finally:
            obs.shutdown()

    try:
        plain_s = arm(False)
        recording_s = arm(True)
    finally:
        sim.observability = prev_obs
        sim.execution_mode = prev_mode
        sim._build_compiled()
    return {
        "round_s_plain": round(plain_s, 5),
        "round_s_recording": round(recording_s, 5),
        "overhead_pct": (
            round(100.0 * (recording_s - plain_s) / plain_s, 2)
            if plain_s > 0 else None
        ),
        "rounds": TIMED_ROUNDS,
    }


def timed_fleet_overhead(sim, timing: bool = True) -> dict:
    """Fleet-ledger block (fleet-telescope PR acceptance metric): per-round
    wall of the REAL ``fit()`` driver loop with the per-client lifetime
    ledger off vs on (the default), plus the ledger's host footprint after
    a registry-scale synthetic absorb.

    The footprint number is pure host work (no device, no compile) so it
    always lands — on the CPU fallback only the timing arms come back
    null. The ledger stores O(participated) records and registry-size-
    invariant sketches, so ``ledger_bytes_at_N`` tracks the SAMPLED
    population, not the 100k registry it is drawn from."""
    import numpy as np

    from fl4health_tpu.observability import (
        MetricsRegistry,
        Observability,
        Tracer,
    )
    from fl4health_tpu.observability.fleet import FleetLedger

    synth_rounds, synth_k, synth_registry = 256, 64, 100_000
    rng = np.random.default_rng(0)
    ledger = FleetLedger()
    for rnd in range(1, synth_rounds + 1):
        ids = rng.choice(synth_registry, size=synth_k, replace=False)
        ledger.absorb_round(
            rnd, ids,
            losses=rng.random(synth_k),
            staleness_pool=rng.integers(0, 8, synth_k),
            registry_size=synth_registry,
        )
    out: dict = {
        "ledger_bytes_at_N": int(ledger.nbytes()),
        "synthetic": {
            "rounds": synth_rounds,
            "participants_per_round": synth_k,
            "registry_size": synth_registry,
            "clients_seen": len(ledger),
        },
        "round_s_plain": None,
        "round_s_fleet": None,
        "overhead_pct": None,
        "rounds": TIMED_ROUNDS,
    }
    if not timing:
        return out

    prev_obs = sim.observability
    prev_mode = sim.execution_mode
    # pipelined: the mode whose consumer-thread epilogue hosts the absorb
    # (the chunked scan would amortize it invisibly)
    sim.execution_mode = "pipelined"

    def arm(fleet: bool) -> float:
        obs = Observability(
            enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
            sync_device=False, flight_recorder=False, fleet_ledger=fleet,
        )
        sim.observability = obs
        try:
            sim._build_compiled()
            sim.fit(1)  # warmup: every program fit() touches is compiled
            t0 = time.perf_counter()
            sim.fit(TIMED_ROUNDS)
            return (time.perf_counter() - t0) / TIMED_ROUNDS
        finally:
            obs.shutdown()

    try:
        plain_s = arm(False)
        fleet_s = arm(True)
    finally:
        sim.observability = prev_obs
        sim.execution_mode = prev_mode
        sim._build_compiled()
    out.update(
        round_s_plain=round(plain_s, 5),
        round_s_fleet=round(fleet_s, 5),
        overhead_pct=(
            round(100.0 * (fleet_s - plain_s) / plain_s, 2)
            if plain_s > 0 else None
        ),
    )
    return out


def timed_ops_overhead(sim, timing: bool = True) -> dict:
    """Operations-plane block (ops-plane PR acceptance metric): per-round
    wall of the REAL ``fit()`` driver loop with plain observability vs the
    full ops plane armed — SLO engine evaluating every objective in the
    epilogue plus the admin retune endpoint (time-series feed, burn-rate
    windows, boundary drain check). The claim under test: the whole plane
    is O(1) host work per round in the consumer epilogue, so it must cost
    ~nothing against the device round.

    On the CPU fallback the timing arms come back null (None, never 0.0)
    — same convention as every other overhead block. Because this block
    feeds a bench_gate band (OPS_OVERHEAD_PCT_MAX), the arms alternate
    A/B/A/B and each side keeps its best pass: per-round plane cost is in
    the tens of microseconds, far below the fit()-to-fit() jitter a single
    pass would report as signal."""
    from fl4health_tpu.observability import (
        MetricsRegistry,
        Observability,
        SLOPolicy,
        Tracer,
    )

    # more timed rounds than the other blocks: the per-fit spin-up
    # (pipeline threads, manifest build) is noise shared by both arms, and
    # the band check needs it amortized away
    rounds = max(TIMED_ROUNDS, 10)
    out: dict = {
        "round_s_plain": None,
        "round_s_ops_plane": None,
        "overhead_pct": None,
        "rounds": rounds,
    }
    if not timing:
        return out

    prev_obs = sim.observability
    prev_mode = sim.execution_mode
    # pipelined: the mode whose consumer-thread epilogue hosts the SLO
    # evaluation, and the only mode the armed admin endpoint runs under
    sim.execution_mode = "pipelined"

    def arm(ops: bool) -> float:
        kwargs: dict = {}
        if ops:
            # every objective armed so the engine does its full per-round
            # work; thresholds generous enough to stay in-budget (a breach
            # only adds one transition event, not steady-state cost)
            kwargs["slo"] = SLOPolicy(
                min_rounds_per_hour=0.001,
                max_eval_loss=1e9,
                stall_rounds=10_000,
                max_bytes_per_client=1e15,
                max_mttr_s=1e9,
                max_straggler_p99=1e9,
            )
            kwargs["admin_token"] = "bench-ops-overhead"
        # introspection off in BOTH arms: the per-fit HLO parse is ~100ms
        # of high-variance host work identical across arms — amortized
        # over TIMED_ROUNDS it would swamp the tens-of-microseconds delta
        # this block exists to measure
        obs = Observability(
            enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
            sync_device=False, flight_recorder=False, introspection=False,
            **kwargs,
        )
        sim.observability = obs
        try:
            sim._build_compiled()
            sim.fit(1)  # warmup: every program fit() touches is compiled
            t0 = time.perf_counter()
            sim.fit(rounds)
            return (time.perf_counter() - t0) / rounds
        finally:
            obs.shutdown()

    try:
        plain_s = min(arm(False), arm(False))
        ops_s = min(arm(True), arm(True))
        plain_s = min(plain_s, arm(False))
        ops_s = min(ops_s, arm(True))
    finally:
        sim.observability = prev_obs
        sim.execution_mode = prev_mode
        sim._build_compiled()
    out.update(
        round_s_plain=round(plain_s, 5),
        round_s_ops_plane=round(ops_s, 5),
        overhead_pct=(
            round(100.0 * (ops_s - plain_s) / plain_s, 2)
            if plain_s > 0 else None
        ),
    )
    return out


def timed_resilience_overhead(sim) -> dict:
    """Device cost of Byzantine-robust aggregation (resilience PR
    acceptance metric): per-round time of the compiled fit round under the
    plain weighted-mean FedAvg vs the robust trimmed-mean reduction.

    RobustFedAvg's state is the plain FedAvgState, so the strategy swaps in
    place (same server-state pytree, no sim rebuild beyond the round
    programs); both loops are fenced. The robust reduction replaces one
    masked weighted sum with a per-coordinate sort — the number this block
    exists to track on real accelerators."""
    from fl4health_tpu.resilience import RobustFedAvg

    plain_s = _timed_round_loop(sim, sim._fit_round)
    prev_strategy = sim.strategy
    method = os.environ.get("FL4HEALTH_BENCH_ROBUST_METHOD", "trimmed_mean")
    sim.strategy = RobustFedAvg(method)
    try:
        sim._build_compiled()
        robust_s = _timed_round_loop(sim, sim._fit_round)
    finally:
        sim.strategy = prev_strategy
        sim._build_compiled()
    return {
        "round_s_plain": round(plain_s, 5),
        "round_s_robust": round(robust_s, 5),
        "robust_method": method,
        "overhead_pct": (
            round(100.0 * (robust_s - plain_s) / plain_s, 2)
            if plain_s > 0 else None
        ),
        "rounds": TIMED_ROUNDS,
    }


def timed_compression_overhead(sim, timing: bool = True) -> dict:
    """Compressed-exchange block (communication-efficiency PR acceptance
    metric): real wire bytes of one client's update through the compressed
    codec vs the dense frame, plus the device cost of compiling the
    in-graph encode->decode channel into the fit round.

    Bytes are measured on REAL frames (transport/codec.py): one dense
    ``encode`` vs one ``encode_compressed`` of the global params under the
    benched config — header, sidecars and CRC included, so the ratio is
    the number a cross-silo deployment would see. Bytes are host-side and
    cheap, so they land in EVERY artifact (the >=8x claim survives the
    CPU fallback); ``timing=False`` skips only the round-time arms
    (``round_s_*`` come back null). Timing swaps a CompressingStrategy
    wrapper (with its CompressedExchangeState) in place, mirrors the
    resilience block's discipline, and restores the original
    strategy/state."""
    from fl4health_tpu.compression import CompressingStrategy, CompressionConfig
    from fl4health_tpu.transport.codec import encode, encode_compressed

    topk = float(os.environ.get("FL4HEALTH_BENCH_TOPK", "0.1"))
    bits = int(os.environ.get("FL4HEALTH_BENCH_QUANT_BITS", "8"))
    cfg = CompressionConfig(topk_fraction=topk, quant_bits=bits)

    # Host copy BEFORE any timing dispatch: _timed_round_loop's donated
    # dispatches invalidate the device buffers sim.server_state aliases,
    # so on TPU/GPU a live reference here would be a deleted array by the
    # time the compressed arm initializes its wrapper state.
    import jax

    gp = jax.device_get(sim.strategy.global_params(sim.server_state))
    bytes_logical = len(encode(gp))
    bytes_wire = len(encode_compressed(gp, cfg))

    plain_s = compressed_s = None
    if timing:
        plain_s = _timed_round_loop(sim, sim._fit_round)
        prev_strategy, prev_state = sim.strategy, sim.server_state
        sim.strategy = CompressingStrategy(
            prev_strategy, cfg, n_clients=sim.n_clients
        )
        sim.server_state = sim.strategy.init(gp)
        try:
            sim._build_compiled()
            compressed_s = _timed_round_loop(sim, sim._fit_round)
        finally:
            sim.strategy, sim.server_state = prev_strategy, prev_state
            sim._build_compiled()
    return {
        "bytes_logical": bytes_logical,
        "bytes_wire": bytes_wire,
        "ratio": (round(bytes_logical / bytes_wire, 3)
                  if bytes_wire > 0 else None),
        "round_s_plain": round(plain_s, 5) if plain_s is not None else None,
        "round_s_compressed": (round(compressed_s, 5)
                               if compressed_s is not None else None),
        "topk_fraction": topk,
        "quant_bits": bits,
        "rounds": TIMED_ROUNDS if timing else 0,
    }


def timed_precision_block(timing: bool = True) -> dict:
    """Mixed-precision block (the roofline-path PR acceptance metric):
    engine-level bf16 compute with f32 master weights
    (``FederatedSimulation(precision=PrecisionConfig("bfloat16"))``) vs the
    plain f32 build, on the benched CIFAR config with the MODEL dtype
    pinned to f32 so the PrecisionConfig is the ONLY difference between
    arms.

    ``loss_delta`` (final-round training-loss gap between the arms over
    TIMED_ROUNDS identical-seed rounds) is always measured — it is the
    cheap half and the accuracy side of the claim survives the CPU
    fallback. ``timing=False`` skips only the round-time arms (round_s_*/
    mfu_pct_* come back null, the standard CPU-fallback annotation): bf16
    is EMULATED on XLA:CPU, so a fallback timing would report the emulation
    tax, not the MXU speedup. Per-arm ``mfu_pct`` uses each arm's own
    compiled cost-model FLOPs over its measured round time against the
    chip's bf16 peak — null (never 0.0) where either is unknown."""
    from fl4health_tpu.precision import PrecisionConfig

    import jax.numpy as jnp

    dtype_name = os.environ.get("FL4HEALTH_BENCH_PRECISION_DTYPE", "bfloat16")
    _, device_kind = _provenance()
    peak = device_specs.peak_bf16_flops(device_kind)

    def arm(precision):
        round_s = flops = None
        if timing:
            _, sim = make_sim("cifar_cnn", precision=precision,
                              model_dtype=jnp.float32)
            compiled, prog = compile_fit_round(sim)
            flops = prog.flops
            round_s = timed_compiled_rounds(sim, compiled)
            del sim
        # loss trajectory on a FRESH sim (the timed dispatches donated the
        # first sim's state buffers); identical seeds across arms
        _, sim = make_sim("cifar_cnn", precision=precision,
                          model_dtype=jnp.float32)
        loss = float(sim.fit(TIMED_ROUNDS)[-1].fit_losses["backward"])
        return round_s, flops, loss

    def mfu(flops, round_s):
        if not (peak and flops and round_s):
            return None
        return round(100.0 * flops / round_s / peak, 2)

    f32_s, f32_flops, f32_loss = arm(None)
    lp_s, lp_flops, lp_loss = arm(PrecisionConfig(dtype_name))
    return {
        "compute_dtype": dtype_name,
        "round_s_f32": round(f32_s, 5) if f32_s is not None else None,
        "round_s_bf16": round(lp_s, 5) if lp_s is not None else None,
        "speedup": (round(f32_s / lp_s, 3) if f32_s and lp_s else None),
        # per-arm MFU, attributed to the dtype that produced the wall time
        # (both against the chip's bf16 peak — the roofline of record)
        "mfu_pct_f32": mfu(f32_flops, f32_s),
        "mfu_pct_bf16": mfu(lp_flops, lp_s),
        "loss_f32": round(f32_loss, 5),
        "loss_bf16": round(lp_loss, 5),
        "loss_delta": round(abs(lp_loss - f32_loss), 5),
        "rounds": TIMED_ROUNDS,
    }


def timed_recovery_block(timing: bool = True) -> dict:
    """Recovery block (the preemption-survivability PR acceptance metric):
    durable state-checkpoint write/restore latency and frame bytes on a
    compact federated config, plus the end-to-end resume-overhead ratio —
    the wall of [run killed at the midpoint + restore + finish] over the
    uninterrupted run's wall. A ratio near 1.0 is the claim: preemption is
    a detour, not a restart.

    Write/restore latencies are pure host I/O (serialize + atomic publish
    + CRC verify), exact on any backend, and always land; ``timing=False``
    (the CPU-fallback annotation) nulls only the fit-wall resume arm —
    XLA:CPU round walls are harness health, not speed claims."""
    import shutil
    import tempfile

    import jax

    from fl4health_tpu.checkpointing.state import SimulationStateCheckpointer

    def make(ckpt_dir=None, every=1):
        import optax

        from fl4health_tpu.clients import engine as _engine
        from fl4health_tpu.datasets.synthetic import synthetic_classification
        from fl4health_tpu.metrics import efficient
        from fl4health_tpu.metrics.base import MetricManager
        from fl4health_tpu.models.cnn import Mlp
        from fl4health_tpu.server.simulation import (
            ClientDataset,
            FederatedSimulation,
        )
        from fl4health_tpu.strategies.fedavg import FedAvg

        datasets = []
        for i in range(8):
            x, y = synthetic_classification(
                jax.random.PRNGKey(i), 48, (8,), 3, class_sep=1.5
            )
            datasets.append(ClientDataset(x[:40], y[:40], x[40:], y[40:]))
        model = _engine.from_flax(Mlp(features=(16,), n_outputs=3))
        logic = _engine.ClientLogic(model, _engine.masked_cross_entropy)
        ck = None
        if ckpt_dir is not None:
            ck = SimulationStateCheckpointer(ckpt_dir, keep=2,
                                             checkpoint_every=every)
        return FederatedSimulation(
            logic=logic, tx=optax.sgd(0.05), strategy=FedAvg(),
            datasets=datasets, batch_size=8,
            metrics=MetricManager((efficient.accuracy(),)),
            local_steps=LOCAL_STEPS, seed=7, state_checkpointer=ck,
        )

    tmp = tempfile.mkdtemp(prefix="fl4h_bench_recovery_")
    try:
        # -- write/restore latency + frame bytes (host I/O, always) ------
        sim = make()
        sim.fit(1)  # realistic state: one optimizer step behind it
        trees = jax.device_get({"server_state": sim.server_state,
                                "client_states": sim.client_states})
        ck = SimulationStateCheckpointer(os.path.join(tmp, "lat"), keep=2)
        write_s = []
        for i in range(5):
            t0 = time.perf_counter()
            ck.save_simulation_snapshot(trees, i + 1, sim.n_clients, [])
            write_s.append(time.perf_counter() - t0)
        frame_bytes = int(ck.last_save_stats["bytes"])
        sim2 = make()
        t0 = time.perf_counter()
        next_round = ck.load_simulation(sim2)
        restore_s = time.perf_counter() - t0
        assert next_round == 6
        out = {
            "write_ms_median": round(sorted(write_s)[2] * 1000.0, 3),
            "restore_ms": round(restore_s * 1000.0, 3),
            "frame_bytes": frame_bytes,
            "ring_generations": len(ck.generations()),
        }
        if not timing:
            out.update({"fit_s_uninterrupted": None,
                        "fit_s_killed_plus_resumed": None,
                        "resume_overhead_ratio": None, "rounds": 0})
            return out
        # -- resume-overhead ratio (fit arms) ----------------------------
        rounds = max(TIMED_ROUNDS * 2, 6)
        mid = rounds // 2
        # unmeasured warmup: every arm below reuses these compiles (via
        # the persistent cache), so the ratio compares I/O + dispatch, not
        # which arm happened to pay XLA first
        make(os.path.join(tmp, "warm"), every=mid).fit(rounds)
        t0 = time.perf_counter()
        make(os.path.join(tmp, "full"), every=mid).fit(rounds)
        full_wall = time.perf_counter() - t0
        drill_dir = os.path.join(tmp, "drill")
        t0 = time.perf_counter()
        make(drill_dir, every=mid).fit(mid)  # the "killed" half
        t_part1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        make(drill_dir, every=mid).fit(rounds)  # restore + finish
        t_resumed = time.perf_counter() - t0
        out.update({
            "fit_s_uninterrupted": round(full_wall, 5),
            "fit_s_killed_plus_resumed": round(t_part1 + t_resumed, 5),
            "resume_overhead_ratio": round(
                (t_part1 + t_resumed) / full_wall, 3
            ) if full_wall > 0 else None,
            "rounds": rounds,
        })
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def timed_sweep_block(timing: bool = True) -> dict:
    """Sweep block (the shared-compilation PR acceptance metric): run a
    24-cell {2 strategies x 2 client algorithms x 2 partitioners x 2
    seeds x 2 server-lr values} grid through ``fl4health_tpu/sweep/`` and
    record the compile-amortization numbers — {cells, buckets,
    programs_compiled, compile_s_total, cells_per_compile, wall_s}. The
    acceptance bar is ``programs_compiled <= cells / 3``; here the grid
    dispatches through 4 program groups (strategy x client), so a healthy
    run reports 24 cells over ~4 compiled programs.

    Counts/compile facts are exact on any backend and always land;
    ``timing=False`` (the CPU-fallback annotation) nulls only the
    throughput fields (steps_per_s_median, cells_per_s) — XLA:CPU walls
    are harness health, not speed claims."""
    import jax
    import numpy as np
    import optax

    from fl4health_tpu.clients import engine as client_engine
    from fl4health_tpu.clients.ditto import MrMtlClientLogic
    from fl4health_tpu.datasets.synthetic import synthetic_classification
    from fl4health_tpu.models.cnn import Mlp
    from fl4health_tpu.server.simulation import ClientDataset
    from fl4health_tpu.strategies.fedavg import FedAvg
    from fl4health_tpu.strategies.fedopt import fed_adam
    from fl4health_tpu.sweep import SweepSpec, run_sweep

    n_classes = 3

    def model():
        return client_engine.from_flax(Mlp(features=(16,),
                                           n_outputs=n_classes))

    def partitioner(salt):
        def build(cohort):
            out = []
            for i in range(cohort):
                x, y = synthetic_classification(
                    jax.random.PRNGKey(1000 * salt + i), 48, (8,), n_classes
                )
                n = 28 + 4 * ((i + salt) % 3)  # unequal non-IID-ish sizes
                out.append(ClientDataset(
                    np.asarray(x[:n]), np.asarray(y[:n]),
                    np.asarray(x[40:]), np.asarray(y[40:]),
                ))
            return out
        return build

    rounds = int(os.environ.get("FL4HEALTH_BENCH_SWEEP_ROUNDS", 3))
    spec = SweepSpec(
        strategies={"fedavg": FedAvg, "fedadam": lambda: fed_adam(0.1)},
        clients={
            "sgd": lambda: client_engine.ClientLogic(
                model(), client_engine.masked_cross_entropy
            ),
            "mrmtl": lambda: MrMtlClientLogic(
                model(), client_engine.masked_cross_entropy, lam=0.5
            ),
        },
        partitioners={"dir0": partitioner(0), "dir1": partitioner(1)},
        rounds=rounds, batch_size=8, local_steps=2,
        tx=lambda: optax.sgd(0.05),
        seeds=(5, 7), cohort_sizes=(3,),
        scalars={"server_lr": (0.1, 0.3)},
    )
    result = run_sweep(spec)
    block = result.bench_block()
    steps = [r.steps_per_s for r in result.cells]
    block["steps_per_s_median"] = (
        round(float(np.median(steps)), 3) if timing else None
    )
    block["cells_per_s"] = (
        round(len(result.cells) / result.wall_s, 3)
        if timing and result.wall_s > 0 else None
    )
    best = result.leaderboard()[0]
    block["best_cell"] = best.cell.label()
    block["best_final_eval_loss"] = round(best.final_eval_loss, 5)
    block["rounds"] = rounds
    return block


def timed_cohort_block(timing: bool = True) -> dict:
    """Cohort-slot block (the O(sampled-cohort) PR acceptance metric):
    grow the REGISTRY 1k -> 100k clients at a fixed K=64 slot count and
    show (a) the compiled slot program's XLA cost/memory analysis is
    IDENTICAL across registry sizes (exact on any backend — the O(K)
    claim), and (b) per-round wall time stays flat (<= ~1.2x) as N grows,
    with the host staging overlapped behind device work
    (``stage_ms``/``scatter_ms``/device-wait medians per N).

    The flatness ratio is a SAME-BOX relative measurement, so it lands on
    any backend (the CPU-fallback note labels it harness health, not a
    TPU claim); ``timing=False`` nulls only the staging-vs-device overlap
    ratio — a CPU round is too small to hide host staging behind — while
    the introspection equality and per-round attribution always land.
    Knobs: FL4HEALTH_BENCH_COHORT_SLOTS (64),
    FL4HEALTH_BENCH_COHORT_SIZES ("1000,10000,100000"),
    FL4HEALTH_BENCH_COHORT_ROUNDS (4; round 1 is compile warmup)."""
    import jax
    import numpy as np
    import optax

    from fl4health_tpu.clients import engine as client_engine
    from fl4health_tpu.datasets.registry_presets import (
        dirichlet_registry_source,
    )
    from fl4health_tpu.datasets.synthetic import synthetic_classification
    from fl4health_tpu.metrics.base import MetricManager
    from fl4health_tpu.models.cnn import Mlp
    from fl4health_tpu.observability import Observability
    from fl4health_tpu.server.client_manager import FixedFractionManager
    from fl4health_tpu.server.registry import CohortConfig
    from fl4health_tpu.server.simulation import FederatedSimulation
    from fl4health_tpu.strategies.fedavg import FedAvg

    n_classes = 5
    slots = int(os.environ.get("FL4HEALTH_BENCH_COHORT_SLOTS", 64))
    sizes = [
        int(s) for s in os.environ.get(
            "FL4HEALTH_BENCH_COHORT_SIZES", "1000,10000,100000"
        ).split(",")
    ]
    rounds = max(int(os.environ.get("FL4HEALTH_BENCH_COHORT_ROUNDS", 4)), 2)
    x, y = synthetic_classification(
        jax.random.PRNGKey(0), 4096, (16,), n_classes
    )
    x, y = np.asarray(x), np.asarray(y)

    def median(vals):
        return round(float(np.median(vals)), 3) if vals else None

    arms = []
    program_facts = []
    from fl4health_tpu.observability.registry import MetricsRegistry as _Reg

    for n in sizes:
        source = dirichlet_registry_source(x, y, n, beta=0.5, seed=7)
        # per-arm PRIVATE registry: the default is process-global, and a
        # cumulative event log would smear one arm's medians into the next
        obs = Observability(enabled=True, introspection=True,
                            registry=_Reg())
        sim = FederatedSimulation(
            logic=client_engine.ClientLogic(
                client_engine.from_flax(
                    Mlp(features=(64, 32), n_outputs=n_classes)
                ),
                client_engine.masked_cross_entropy,
            ),
            tx=optax.sgd(0.05),
            strategy=FedAvg(),
            datasets=source,
            batch_size=16,
            metrics=MetricManager(()),
            local_steps=4,
            seed=5,
            cohort=CohortConfig(slots=slots),
            client_manager=FixedFractionManager(n, slots / n),
            observability=obs,
        )
        t0 = time.perf_counter()
        sim.fit(rounds)
        wall = time.perf_counter() - t0
        events = [e for e in obs.registry.events if e["event"] == "round"]
        steady = events[1:]  # round 1 carries the compiles
        programs = {
            e["name"]: e for e in obs.registry.events
            if e["event"] == "program"
        }
        # telemetry-enabled observability introspects the _t variants
        fitp = programs.get("fit_round") or programs.get("fit_round_t") or {}
        program_facts.append({
            "registry_size": n,
            "flops": fitp.get("flops"),
            "peak_hbm_bytes": fitp.get("peak_hbm_bytes"),
        })
        arms.append({
            "registry_size": n,
            "cohort_slots": slots,
            "rounds": rounds,
            "wall_s_total": round(wall, 3),
            "round_ms_median": median(
                [1e3 * (e["fit_s"] + e["eval_s"]) for e in steady]
            ),
            "device_wait_ms_median": median(
                [1e3 * e["device_wait_s"] for e in steady]
            ),
            "stage_ms_median": median([e["stage_ms"] for e in steady]),
            "gather_ms_median": median([e["gather_ms"] for e in steady]),
            "scatter_ms_median": median([e["scatter_ms"] for e in steady]),
            "registry_dirty_rows": (
                steady[-1]["registry_dirty_rows"] if steady else None
            ),
        })
    flops_vals = {p["flops"] for p in program_facts}
    hbm_vals = {p["peak_hbm_bytes"] for p in program_facts}
    r0 = arms[0]["round_ms_median"]
    rN = arms[-1]["round_ms_median"]
    stage = arms[-1]["stage_ms_median"]
    dev = arms[-1]["device_wait_ms_median"]
    return {
        "cohort_slots": slots,
        "registry_sizes": sizes,
        "arms": arms,
        # THE O(K) claim — exact on any backend: one compiled program
        # shape/cost for every registry size at fixed K
        "program_flops_identical": len(flops_vals) == 1,
        "program_peak_hbm_identical": len(hbm_vals) == 1,
        "program_flops": program_facts[0]["flops"],
        "program_peak_hbm_bytes": program_facts[0]["peak_hbm_bytes"],
        # wall flatness: a SAME-BOX ratio (not an absolute speed claim),
        # so it lands on any backend — the CPU-fallback note still applies
        "round_time_ratio_maxN_vs_minN": (
            round(rN / r0, 3) if r0 and rN else None
        ),
        # staging overlap: a real-device claim (a CPU round is too small
        # to hide host staging behind), nulled on the fallback
        "staging_vs_device_ratio": (
            round(stage / dev, 3) if timing and stage and dev else None
        ),
    }


def timed_cohort_chunk_block(timing: bool = True) -> dict:
    """Chunked-cohort dispatch-amortization block (the O(rounds/R)
    host-barrier PR metric): run the SAME subsampled cohort fit pipelined
    (R=1 host-drawn baseline) and chunked at R in {1, 8, 32} rounds per
    dispatch, and report MEASURED host round-trips per round via the
    ``fl_cohort_host_roundtrips_total`` counter plus dispatch and compile
    counts — all exact on any backend. Wall time is the only timing
    field, nulled on the CPU fallback. The arms' final params are
    compared bitwise (the parity claim rides the artifact, not just the
    test suite). Knobs: FL4HEALTH_BENCH_COHORT_CHUNK_ROUNDS (32),
    FL4HEALTH_BENCH_COHORT_CHUNK_REGISTRY (256),
    FL4HEALTH_BENCH_COHORT_CHUNK_SLOTS (16)."""
    import tempfile

    import jax
    import numpy as np
    import optax

    from fl4health_tpu.checkpointing.state import SimulationStateCheckpointer
    from fl4health_tpu.clients import engine as client_engine
    from fl4health_tpu.datasets.registry_presets import (
        dirichlet_registry_source,
    )
    from fl4health_tpu.datasets.synthetic import synthetic_classification
    from fl4health_tpu.metrics.base import MetricManager
    from fl4health_tpu.models.cnn import Mlp
    from fl4health_tpu.observability import Observability
    from fl4health_tpu.observability.registry import MetricsRegistry
    from fl4health_tpu.server.client_manager import FixedFractionManager
    from fl4health_tpu.server.registry import CohortConfig
    from fl4health_tpu.server.simulation import FederatedSimulation
    from fl4health_tpu.strategies.fedavg import FedAvg

    n_classes = 5
    rounds = max(
        int(os.environ.get("FL4HEALTH_BENCH_COHORT_CHUNK_ROUNDS", 32)), 2
    )
    n = int(os.environ.get("FL4HEALTH_BENCH_COHORT_CHUNK_REGISTRY", 256))
    slots = int(os.environ.get("FL4HEALTH_BENCH_COHORT_CHUNK_SLOTS", 16))
    x, y = synthetic_classification(
        jax.random.PRNGKey(0), 2048, (16,), n_classes
    )
    x, y = np.asarray(x), np.asarray(y)

    def run(mode, r, ckpt_dir):
        reg = MetricsRegistry()  # PRIVATE: the default registry is
        # process-global and would smear counters across arms
        obs = Observability(enabled=True, registry=reg)
        sim = FederatedSimulation(
            logic=client_engine.ClientLogic(
                client_engine.from_flax(
                    Mlp(features=(32,), n_outputs=n_classes)
                ),
                client_engine.masked_cross_entropy,
            ),
            tx=optax.sgd(0.05),
            strategy=FedAvg(),
            datasets=dirichlet_registry_source(x, y, n, beta=0.5, seed=7),
            batch_size=16,
            metrics=MetricManager(()),
            local_steps=2,
            seed=5,
            cohort=CohortConfig(slots=slots),
            client_manager=FixedFractionManager(n, slots / n),
            execution_mode=mode,
            observability=obs,
            # checkpoint_every IS the chunk length R: boundaries force one
            # dispatch per R rounds; R == rounds runs the whole fit as one
            # scan with no checkpointer at all
            state_checkpointer=(
                None if r >= rounds else SimulationStateCheckpointer(
                    ckpt_dir, checkpoint_every=r, keep=1
                )
            ),
        )
        t0 = time.perf_counter()
        sim.fit(rounds)
        wall = time.perf_counter() - t0
        events = [e for e in reg.events if e["event"] == "round"]
        trips = reg.counter("fl_cohort_host_roundtrips_total").value
        return {
            "mode": mode,
            "rounds_per_dispatch": r,
            "rounds": rounds,
            # the measured O(rounds/R) claim — exact on any backend
            "host_roundtrips_total": int(trips),
            "host_roundtrips_per_round": round(trips / rounds, 4),
            "dispatches": int(trips),
            "compiles_total": int(
                sum(e.get("compiles", 0) for e in events)
            ),
            "cohort_draw": (
                events[-1].get("cohort_draw") if events else None
            ),
            "wall_s_total": round(wall, 3) if timing else None,
        }, np.asarray(
            jax.flatten_util.ravel_pytree(jax.device_get(sim.global_params))[0]
        )

    arms, params = [], []
    with tempfile.TemporaryDirectory() as td:
        arm, p = run("pipelined", 1, os.path.join(td, "pipelined"))
        arms.append(arm)
        params.append(p)
        for r in (1, 8, 32):
            r = min(r, rounds)
            arm, p = run("chunked", r, os.path.join(td, f"chunk_{r}"))
            arms.append(arm)
            params.append(p)
    base = arms[0]
    chunked_max = arms[-1]
    return {
        "registry_size": n,
        "cohort_slots": slots,
        "rounds": rounds,
        "arms": arms,
        # every arm must land on the pipelined baseline's params BITWISE —
        # the parity discipline the chunk lengths ride on
        "params_bitwise_identical": all(
            np.array_equal(params[0], p) for p in params[1:]
        ),
        # the acceptance ratio: host round-trips per round must shrink by
        # >= R/2 at the largest chunk length
        "roundtrip_reduction_at_max_r": round(
            base["host_roundtrips_total"]
            / max(chunked_max["host_roundtrips_total"], 1), 3
        ),
    }


def timed_async_block(timing: bool = True) -> dict:
    """Buffered-async block (the tail-independence PR acceptance metric):
    sync-vs-async round CADENCE and final loss under one fixed straggler
    ``FaultPlan`` — 2 of 8 clients at 5x compute time.

    The cadence side reads off the VIRTUAL clock (the same deterministic
    compute-time model both modes schedule from, ``server/async_schedule``)
    so it is exact, free, and backend-independent: a synchronous round
    costs ``max_c T_c`` (the tail), an async round costs the gap between
    buffer fills. The headline claim: async cadence stays within 1.5x of
    the STRAGGLER-FREE sync cadence while sync degrades toward the tail
    (>= 3x slower), at a final loss within a small delta of sync.

    ``timing=False`` (the CPU-fallback annotation) skips only the real
    fit() loss/wall arms; the virtual-cadence numbers always land."""
    import numpy as np

    from fl4health_tpu.resilience.faults import ClientFault, FaultPlan
    from fl4health_tpu.server.async_schedule import (
        AsyncConfig,
        build_event_plan,
        sync_round_times,
    )

    n_clients = int(os.environ.get("FL4HEALTH_BENCH_ASYNC_CLIENTS", 8))
    if n_clients < 2:
        raise ValueError(
            "FL4HEALTH_BENCH_ASYNC_CLIENTS must be >= 2 (the block needs "
            "at least one straggler AND one fast client)"
        )
    slow_scale = float(os.environ.get("FL4HEALTH_BENCH_ASYNC_SLOW", 5.0))
    k = int(os.environ.get("FL4HEALTH_BENCH_ASYNC_BUFFER", n_clients // 2))
    events = 24  # virtual horizon for the cadence statistics
    acfg = AsyncConfig(buffer_size=k, compute_jitter=0.05)
    # straggler set derived from the cohort (2 of 8 in the claim config):
    # never the whole cohort, so the arrival rate has a fast side to win on
    slow_clients = tuple(range(min(2, n_clients - 1)))
    plan_faults = FaultPlan(client_faults=(
        ClientFault(clients=slow_clients, kind="slow", scale=slow_scale),
    ))
    sync_clean = float(np.mean(sync_round_times(
        acfg, events, n_clients, None
    )))
    sync_straggler = float(np.mean(sync_round_times(
        acfg, events, n_clients, plan_faults
    )))
    plan = build_event_plan(acfg, events, n_clients, plan_faults)
    async_cadence = float(np.mean(plan.cadences()))
    stal = plan.staleness[plan.arrivals > 0]
    out = {
        "n_clients": n_clients,
        "buffer_size": k,
        "slow_clients": len(slow_clients),
        "slow_scale": slow_scale,
        "virtual_events": events,
        # the three cadence numbers the claim is made of (virtual seconds)
        "sync_round_vs_clean": round(sync_clean, 4),
        "sync_round_vs_straggler": round(sync_straggler, 4),
        "async_cadence_vs": round(async_cadence, 4),
        "sync_degradation": round(sync_straggler / sync_clean, 3),
        "async_vs_clean_ratio": round(async_cadence / sync_clean, 3),
        "staleness_mean": round(float(stal.mean()), 3),
        "staleness_max": float(stal.max()),
    }
    if not timing:
        out.update({"final_loss_sync": None, "final_loss_async": None,
                    "loss_delta": None, "round_s_sync": None,
                    "round_s_async": None, "rounds": 0})
        return out

    # loss arms: identical seeds + the SAME FaultPlan; slow faults change
    # no math, so the sync arm is the straggler run's exact trajectory
    import jax
    import optax

    from fl4health_tpu.clients import engine as _engine
    from fl4health_tpu.datasets.synthetic import synthetic_classification
    from fl4health_tpu.metrics import efficient
    from fl4health_tpu.metrics.base import MetricManager
    from fl4health_tpu.models.cnn import Mlp
    from fl4health_tpu.server.simulation import (
        ClientDataset,
        FederatedSimulation,
    )
    from fl4health_tpu.strategies.fedavg import FedAvg

    rounds = max(TIMED_ROUNDS * 2, 6)

    def make(async_config):
        datasets = []
        for i in range(n_clients):
            x, y = synthetic_classification(
                jax.random.PRNGKey(i), 48, (8,), 3, class_sep=1.5
            )
            datasets.append(ClientDataset(x[:40], y[:40], x[40:], y[40:]))
        model = _engine.from_flax(Mlp(features=(16,), n_outputs=3))
        logic = _engine.ClientLogic(model, _engine.masked_cross_entropy)
        return FederatedSimulation(
            logic=logic, tx=optax.sgd(0.05), strategy=FedAvg(),
            datasets=datasets, batch_size=8,
            metrics=MetricManager((efficient.accuracy(),)),
            local_steps=LOCAL_STEPS, seed=7, fault_plan=plan_faults,
            async_config=async_config,
        )

    t0 = time.perf_counter()
    sync_hist = make(None).fit(rounds)
    sync_wall = (time.perf_counter() - t0) / rounds
    t0 = time.perf_counter()
    async_hist = make(acfg).fit(rounds)
    async_wall = (time.perf_counter() - t0) / rounds
    loss_sync = float(sync_hist[-1].eval_losses["checkpoint"])
    loss_async = float(async_hist[-1].eval_losses["checkpoint"])
    out.update({
        "final_loss_sync": round(loss_sync, 5),
        "final_loss_async": round(loss_async, 5),
        "loss_delta": round(abs(loss_async - loss_sync), 5),
        # chip wall per server update (both modes run every client's
        # compute in simulation, so this measures program cost, not the
        # virtual-clock story above)
        "round_s_sync": round(sync_wall, 5),
        "round_s_async": round(async_wall, 5),
        "rounds": rounds,
    })
    return out


def mesh_cohort_size(n_dev: int) -> int:
    """Cohort for the mesh arms: the nearest device-count multiple of
    ``N_CLIENTS`` — rounded DOWN when the configured cohort exceeds the
    device count, but UP to one client per device when it doesn't (an
    8-device host with the default 4-client config benchmarks 8 clients,
    NOT a subset of the main record's 4 — the two mesh arms are compared
    against each other, not against the main bench record)."""
    return max((N_CLIENTS // n_dev) * n_dev, n_dev)


def timed_mesh_rounds() -> dict:
    """Mesh block (FL4HEALTH_BENCH_MESH=1): the SAME chunked-scan rounds
    with the client axis sharded over every visible device
    (``FederatedSimulation(mesh=MeshConfig())``, parallel/program.py) vs
    unsharded — {devices, client_axis, steps_per_s_per_chip} plus the raw
    round walls. Uses the im2col MxuConv lowering (the grouped-conv one is
    rejected by XLA's partitioner under clients-axis sharding) and the
    ``mesh_cohort_size`` cohort (a device-count multiple; see its
    docstring for how it relates to the main record's N_CLIENTS)."""
    import jax

    from fl4health_tpu.parallel.program import MeshConfig

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": f"needs >= 2 devices, have {n_dev}"}
    n_clients = mesh_cohort_size(n_dev)
    _, sim_plain = make_sim("cifar_cnn", conv_impl="mxu",
                            n_clients_override=n_clients)
    round_s_unsharded = timed_chunked_rounds(sim_plain)
    del sim_plain
    _, sim_mesh = make_sim("cifar_cnn", conv_impl="mxu",
                           n_clients_override=n_clients, mesh=MeshConfig())
    round_s_mesh = timed_chunked_rounds(sim_mesh)
    desc = sim_mesh._program_builder.descriptor()
    steps_per_round = n_clients * LOCAL_STEPS
    return {
        "devices": n_dev,
        "client_axis": desc["axes"]["clients"],
        "mesh": desc,
        "n_clients": n_clients,
        "conv_impl": "mxu",
        "steps_per_s_per_chip": round(
            steps_per_round / round_s_mesh / n_dev, 2
        ),
        "steps_per_s_total": round(steps_per_round / round_s_mesh, 2),
        "steps_per_s_unsharded": round(
            steps_per_round / round_s_unsharded, 2
        ),
        "round_s_mesh": round(round_s_mesh, 4),
        "round_s_unsharded": round(round_s_unsharded, 4),
        "speedup_vs_unsharded": round(round_s_unsharded / round_s_mesh, 2),
    }


def timed_eager_round(sim) -> tuple[float, int]:
    """Reference-style dispatch: Python loop over clients, eager step calls,
    per-round full-parameter host round-trip (numpy serialize/deserialize).

    Measured on a subset of clients and extrapolated linearly — eager
    dispatch cost is per-client-sequential by construction, and a full
    64-client eager round (every primitive its own dispatch) would blow the
    bench budget just to measure the slow baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fl4health_tpu.clients import engine

    logic, tx = sim.logic, sim.tx
    step_fn = engine.make_train_step(logic, tx)  # NOT jitted: eager dispatch
    batches = sim._round_batches(0)
    measured = min(int(os.environ.get("FL4HEALTH_BENCH_EAGER_CLIENTS", 4)),
                   sim.n_clients)

    def one_client(c):
        state = jax.tree_util.tree_map(lambda x: x[c], sim.client_states)
        cb = jax.tree_util.tree_map(lambda x: x[c], batches)
        for s in range(LOCAL_STEPS):
            b = jax.tree_util.tree_map(lambda x: x[s], cb)
            state, _ = step_fn(state, None, b)
        return state

    # untimed warmup client: eager op-dispatch compiles are one-time costs
    # that the full-cohort measurement amortized over 64 clients; timing them
    # into a 4-client subset would overstate the eager baseline.
    one_client(0)
    t0 = time.perf_counter()
    collected = []
    for c in range(measured):
        state = one_client(c)
        # Flower-style wire: params -> host numpy list -> back
        nds = [np.asarray(x) for x in jax.tree_util.tree_leaves(state.params)]
        collected.append(nds)
    # host-side aggregation over numpy lists (aggregate_utils.py style)
    agg = [np.mean([c[i] for c in collected], axis=0) for i in range(len(collected[0]))]
    _ = [jnp.asarray(a) for a in agg]
    return (time.perf_counter() - t0) * (sim.n_clients / measured), measured


def _measure_config(model_kind: str, with_eager: bool) -> dict:
    analytic_flops, sim = make_sim(model_kind)
    compiled, prog = compile_fit_round(sim)
    measured_flops = prog.flops  # None where XLA exposes no cost model
    if analytic_flops is not None:
        # Pallas custom-call FLOPs are invisible to the cost model; the
        # analytic count is the honest MFU numerator for those configs —
        # and, under FL4HEALTH_BENCH_ANALYTIC_FLOPS=1, for the dense arm of
        # an A/B too, so both arms share one numerator. Keep the cost-model
        # figure in the artifact for transparency (tflops_measured).
        round_flops = analytic_flops
        cm = (f"{measured_flops / 1e12:.3f}" if measured_flops is not None
              else "nothing")
        flops_source = (
            "analytic_3x_fwd (one numerator for all attention arms; XLA "
            "cost_analysis cannot see Pallas custom-call FLOPs — cost model "
            f"said {cm} TFLOP/round)"
        )
    elif measured_flops:
        round_flops = measured_flops
        flops_source = "xla_cost_analysis"
    else:
        # no measured AND no applicable analytic number: every downstream
        # tflops/mfu field must be null, never a misleading 0.0
        round_flops = None
        flops_source = None
    per_round_dispatch = timed_compiled_rounds(sim, compiled)
    # Two supported execution modes: per-round dispatch and the on-device
    # multi-round scan (one dispatch per TIMED_ROUNDS rounds; semantics
    # pinned equal by tests/server/test_chunked_fit.py). The scan amortizes
    # host->device dispatch latency; the explicit CPU harness run skips it:
    # the scan's extra multi-minute XLA:CPU compile can blow its time
    # budget. Headline = the faster measured mode.
    if os.environ.get("FL4HEALTH_BENCH_FORCE_CPU"):
        per_round_chunked = float("inf")
    else:
        per_round_chunked = timed_chunked_rounds(sim)
    per_round = min(per_round_dispatch, per_round_chunked)
    steps_per_round = sim.n_clients * LOCAL_STEPS
    compiled_sps = steps_per_round / per_round

    achieved_flops = round_flops / per_round if round_flops else None
    _, device_kind = _provenance()
    peak = device_specs.peak_bf16_flops(device_kind)
    hbm_total = device_specs.device_memory_bytes()
    out = {
        "steps_per_sec_per_chip": round(compiled_sps, 2),
        "execution_mode": (
            "chunked_scan" if per_round_chunked <= per_round_dispatch
            else "per_round_dispatch"
        ),
        "rounds_per_dispatch": TIMED_ROUNDS,
        "steps_per_sec_single_dispatch": round(
            steps_per_round / per_round_dispatch, 2
        ),
        "steps_per_sec_chunked": (
            round(steps_per_round / per_round_chunked, 2)
            if per_round_chunked != float("inf") else None
        ),
        # headline tflops = the flops_source numerator over the fastest
        # measured mode; null (not 0.0) when no numerator exists
        "tflops": (round(achieved_flops / 1e12, 3)
                   if achieved_flops else None),
        # measured vs analytic split: tflops_measured is XLA's cost-model
        # count over the same wall time, tflops_analytic the formula count
        "tflops_measured": (round(measured_flops / per_round / 1e12, 3)
                            if measured_flops else None),
        "tflops_analytic": (round(analytic_flops / per_round / 1e12, 3)
                            if analytic_flops else None),
        "mfu_pct": (round(100.0 * achieved_flops / peak, 2)
                    if peak and achieved_flops else None),
        "flops_source": flops_source,
        # compiled fit_round's cost/memory introspection (flops, bytes
        # accessed, HBM footprint, compile wall) — the per-program
        # accounting the observability subsystem records for fit()
        "program_introspection": {"fit_round": prog.as_dict()},
        "hbm_headroom_bytes": (
            int(hbm_total - prog.peak_hbm_bytes)
            if hbm_total is not None and prog.peak_hbm_bytes is not None
            else None
        ),
        "provenance": provenance_block(),
    }
    # Only meaningful against a real accelerator measurement: the bridge on
    # a CPU-fallback number would "model" nothing.
    if peak and achieved_flops:
        out["vs_a100_flower_modeled"] = modeled_vs_a100_flower(achieved_flops)
    if with_eager:
        eager_time, eager_measured = timed_eager_round(sim)
        eager_sps = steps_per_round / eager_time
        out["vs_eager"] = round(compiled_sps / eager_sps, 2)
        # Disclose the extrapolation in the artifact itself (not just the
        # docstring): the eager baseline times this many clients and scales
        # linearly to the full cohort.
        out["eager_clients_measured"] = eager_measured
    # Host-overhead decomposition of the real fit() loop (async-pipeline PR
    # acceptance metric). "auto" runs it on the headline (eager-comparison)
    # config only and skips the CPU fallback, whose tight budget the extra
    # fit rounds would blow; FL4HEALTH_BENCH_HOST_OVERHEAD=1 forces it for
    # ANY config, =0 disables it.
    want_ho = os.environ.get("FL4HEALTH_BENCH_HOST_OVERHEAD", "auto")
    if want_ho == "1" or (
        want_ho == "auto" and with_eager
        and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
    ):
        out["host_overhead"] = timed_fit_overhead(sim)
    # Device cost of compiling in-graph telemetry outputs into the round
    # (observability PR acceptance metric). Same gating shape as
    # host_overhead: FL4HEALTH_BENCH_TELEMETRY=1 forces, =0 disables,
    # "auto" skips only the CPU fallback (whose budget the extra
    # telemetry-variant compile would strain). Runs LAST: it temporarily
    # rebuilds the sim's compiled round programs.
    want_t = os.environ.get("FL4HEALTH_BENCH_TELEMETRY", "auto")
    if want_t == "1" or (
        want_t == "auto"
        and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
    ):
        out["telemetry_overhead"] = timed_telemetry_overhead(sim)
    # Flight-recorder host cost: the real fit() driver loop with the
    # black-box ring off vs on (flight-recorder PR acceptance metric).
    # Same gating shape: FL4HEALTH_BENCH_FLIGHTREC=1 forces, =0 disables,
    # "auto" skips only the CPU fallback (two extra fit() warms would
    # strain its budget).
    want_f = os.environ.get("FL4HEALTH_BENCH_FLIGHTREC", "auto")
    if want_f == "1" or (
        want_f == "auto"
        and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
    ):
        out["flightrec_overhead"] = timed_flightrec_overhead(sim)
    # Fleet-ledger host cost + registry-scale footprint (fleet-telescope
    # PR acceptance metric). FL4HEALTH_BENCH_FLEET=1 forces the full
    # block, =0 disables it; "auto" always lands the exact host footprint
    # numbers (pure-host synthetic absorb) but nulls the fit-wall timing
    # arms on the CPU fallback, like the compression block.
    want_fl = os.environ.get("FL4HEALTH_BENCH_FLEET", "auto")
    if want_fl != "0":
        fl_timing = want_fl == "1" or (
            want_fl == "auto"
            and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
        out["fleet_overhead"] = timed_fleet_overhead(sim, timing=fl_timing)
    # Operations-plane host cost (ops-plane PR acceptance metric): fit()
    # wall with the SLO engine + admin endpoint armed vs plain
    # observability. Opt-in only — FL4HEALTH_BENCH_OPS=1 — because the
    # default sweep already carries four obs-arm rebuild blocks; the
    # timing arms honor the CPU-fallback null rule.
    if os.environ.get("FL4HEALTH_BENCH_OPS") == "1":
        out["ops_overhead"] = timed_ops_overhead(
            sim, timing=not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
    # Robust-aggregator round time vs the plain weighted mean (resilience
    # PR acceptance metric). Same gating shape: FL4HEALTH_BENCH_RESILIENCE
    # =1 forces, =0 disables, "auto" skips only the CPU fallback. Runs
    # after telemetry_overhead — both temporarily rebuild the round
    # programs and restore them.
    want_r = os.environ.get("FL4HEALTH_BENCH_RESILIENCE", "auto")
    if want_r == "1" or (
        want_r == "auto"
        and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
    ):
        out["resilience_overhead"] = timed_resilience_overhead(sim)
    # Compressed-exchange bytes + round time (communication-efficiency PR
    # acceptance metric: >=8x wire reduction at int8 + top-k 10% on the
    # 4-client CIFAR config). FL4HEALTH_BENCH_COMPRESSION=1 forces the
    # full block, =0 disables it; "auto" always measures the (cheap,
    # host-side) wire bytes but skips the round-time arms on the CPU
    # fallback, like the overhead blocks above. Runs last — the timing
    # arms temporarily rebuild the round programs.
    want_c = os.environ.get("FL4HEALTH_BENCH_COMPRESSION", "auto")
    if want_c != "0":
        timing = want_c == "1" or (
            want_c == "auto"
            and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
        out["compression"] = timed_compression_overhead(sim, timing=timing)
    # Mixed-precision arms (the roofline-path PR metric: bf16 engine policy
    # vs f32, {round_s_f32, round_s_bf16, speedup, mfu_pct per arm,
    # loss_delta}). Same gating shape as telemetry/resilience:
    # FL4HEALTH_BENCH_PRECISION=1 forces the full block, =0 disables it,
    # "auto" runs it on the headline config but skips the CPU fallback
    # entirely — the arms each compile + fit a fresh sim, which the
    # fallback's tight budget cannot absorb, and a fallback bf16 timing
    # would report the XLA:CPU emulation tax, not the MXU speedup. The
    # standalone ``python bench.py --precision`` artifact covers the
    # fallback (loss_delta measured, timing arms null-annotated).
    want_p = os.environ.get("FL4HEALTH_BENCH_PRECISION", "auto")
    if want_p == "1" or (
        want_p == "auto" and with_eager
        and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
    ):
        out["precision"] = timed_precision_block(
            timing=not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
            or want_p == "1"
        )
    # Buffered-async cadence + loss arms (the tail-independence PR
    # metric). Same gating shape as telemetry/resilience:
    # FL4HEALTH_BENCH_ASYNC=1 forces the full block, =0 disables it,
    # "auto" runs it but skips the loss/wall fit arms on the CPU fallback
    # (the virtual-clock cadence numbers are free and always land).
    want_a = os.environ.get("FL4HEALTH_BENCH_ASYNC", "auto")
    if want_a != "0":
        a_timing = want_a == "1" or (
            want_a == "auto"
            and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
        out["async"] = timed_async_block(timing=a_timing)
    # Shared-compilation sweep (the scenario-grid PR metric). Same gating
    # shape as telemetry/resilience: FL4HEALTH_BENCH_SWEEP=1 forces the
    # full block, =0 disables it, "auto" runs it but nulls the throughput
    # fields on the CPU fallback (the compile-amortization counts are
    # exact and always land).
    want_s = os.environ.get("FL4HEALTH_BENCH_SWEEP", "auto")
    if want_s != "0":
        s_timing = want_s == "1" or (
            want_s == "auto"
            and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
        out["sweep"] = timed_sweep_block(timing=s_timing)
    # Cohort-slot registry scaling (the O(sampled-cohort) PR metric).
    # Opt-in only — FL4HEALTH_BENCH_COHORT=1 — because the default sweep
    # builds three registries up to 100k clients (tens of seconds of host
    # staging); the standalone `python bench.py --cohort` artifact is the
    # usual lane. =1 forces it in-record with timing fields honored by
    # the CPU-fallback rule.
    if os.environ.get("FL4HEALTH_BENCH_COHORT") == "1":
        out["cohort"] = timed_cohort_block(
            timing=not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
    # Durable checkpoint/resume (the preemption-survivability PR metric).
    # Same gating shape: FL4HEALTH_BENCH_RECOVERY=1 forces the full block,
    # =0 disables it, "auto" always measures the (host-I/O, exact)
    # write/restore latencies + frame bytes but nulls the fit-wall
    # resume-overhead arm on the CPU fallback.
    want_rec = os.environ.get("FL4HEALTH_BENCH_RECOVERY", "auto")
    if want_rec != "0":
        rec_timing = want_rec == "1" or (
            want_rec == "auto"
            and not os.environ.get("FL4HEALTH_BENCH_FORCE_CPU")
        )
        out["recovery"] = timed_recovery_block(timing=rec_timing)
    # Mesh-sharded rounds (the massive-cohort PR metric): opt-in only —
    # FL4HEALTH_BENCH_MESH=1 — because it compiles two extra chunked scans
    # and needs a multi-device backend (single-device runs report skipped).
    if os.environ.get("FL4HEALTH_BENCH_MESH") == "1":
        out["mesh"] = timed_mesh_rounds()
    return out


def run_measurement() -> None:
    """Child-process body. FL4HEALTH_BENCH_ONLY selects the config
    ("cifar" default, or "transformer") so the parent can give each its own
    timeout — a slow/hung transformer compile must never cost the cifar
    headline number."""
    from fl4health_tpu.utils.runtime import configure_compile_cache

    force_cpu = bool(os.environ.get("FL4HEALTH_BENCH_FORCE_CPU"))
    if force_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    configure_compile_cache()
    platform, device_kind = _provenance()
    if platform != "tpu" and not force_cpu:
        # no fallback that hides the device: a measurement that was not
        # asked to run on the CPU never silently does
        raise SystemExit(
            f"bench: the default backend is {platform!r}, not a TPU — "
            "refusing to measure (FL4HEALTH_BENCH_FORCE_CPU=1 is the "
            "explicit CPU harness switch)"
        )
    import jax.numpy as jnp

    dtype = "bfloat16" if _bench_dtype() == jnp.bfloat16 else "float32"

    if os.environ.get("FL4HEALTH_BENCH_ONLY") == "transformer":
        print(json.dumps(_measure_config("transformer", with_eager=False)))
        return
    if os.environ.get("FL4HEALTH_BENCH_ONLY") == "transformer_long":
        out = _measure_config("transformer_long", with_eager=False)
        out["seq_len"] = int(os.environ.get("FL4HEALTH_BENCH_LONGSEQ", 2048))
        # label derives from the SAME predicate that selected the kernel
        out["attention"] = (
            "pallas_flash" if flash_requested(default=True) else "dense"
        )
        print(json.dumps(out))
        return
    if os.environ.get("FL4HEALTH_BENCH_ONLY") == "cifar_noeager":
        # Alt-config child (e.g. the mxu-conv comparison): compiled
        # measurement only, no eager baseline.
        out = _measure_config("cifar_cnn", with_eager=False)
        out["conv_impl"] = _headline_conv_impl()
        print(json.dumps(out))
        return

    cifar = _measure_config("cifar_cnn", with_eager=True)
    # Name reflects the actual config; a CPU-fallback run is labeled as such
    # so it can't be mistaken for the TPU measurement.
    suffix = "_cpu_fallback" if force_cpu else ""
    fallback_note = (
        "CPU-fallback context: XLA:CPU lowers the per-client-weights vmapped "
        "convs to grouped convolutions, which are pathologically slow there "
        "(and can undercut even eager dispatch); the TPU lowering does not "
        "share this. This number certifies the harness runs, not the speed "
        "claim."
    ) if force_cpu else None
    record = {
        "metric": (
            f"fedavg_cifar_cnn_{N_CLIENTS}clients_local_steps"
            f"_per_sec_per_chip{suffix}"
        ),
        "value": cifar["steps_per_sec_per_chip"],
        "unit": "local_steps/sec/chip",
        # PROXY: compiled-vs-eager on the same chip, not an A100 Flower run.
        "vs_baseline": cifar.get("vs_eager"),
        "vs_baseline_kind": "eager_jax_same_chip_proxy",
        # The eager side times this many clients and extrapolates linearly
        # to the full cohort (see timed_eager_round).
        "eager_clients_measured": cifar.get("eager_clients_measured"),
        "platform": platform,
        "device_kind": device_kind,
        "dtype": dtype,
        # No real CIFAR/MNIST exists on this box (zero egress); the moment a
        # real corpus drives the bench this field must say so.
        "data_provenance": "synthetic",
        # null (never 0.0) when no measured or applicable analytic FLOP
        # number exists for this backend/config
        "tflops": cifar["tflops"],
        "tflops_measured": cifar["tflops_measured"],
        "tflops_analytic": cifar["tflops_analytic"],
        "mfu_pct": cifar["mfu_pct"],
        "flops_source": cifar["flops_source"],
        # per-program XLA cost/memory introspection + HBM headroom
        "program_introspection": cifar["program_introspection"],
        "hbm_headroom_bytes": cifar["hbm_headroom_bytes"],
        # Assumption-based bridge to BASELINE.json's >=10x-vs-A100-Flower
        # north star (see modeled_vs_a100_flower); null off-TPU.
        "vs_a100_flower_modeled": cifar.get("vs_a100_flower_modeled"),
        "conv_impl": _headline_conv_impl(),
        "execution_mode": cifar["execution_mode"],
        "rounds_per_dispatch": cifar["rounds_per_dispatch"],
        "steps_per_sec_single_dispatch": cifar["steps_per_sec_single_dispatch"],
        "steps_per_sec_chunked": cifar["steps_per_sec_chunked"],
        # per-round host/device busy split of the real fit() driver loop
        # (host_busy_s, device_busy_s, host_device_ratio) — the async-round-
        # pipeline win, tracked per BENCH_* artifact from that PR onward.
        "host_overhead": cifar.get("host_overhead"),
        # in-graph telemetry and robust-aggregation round-time costs
        # ({round_s_plain, round_s_telemetry/round_s_robust, overhead_pct}),
        # tracked per BENCH_* artifact from their PRs onward
        "telemetry_overhead": cifar.get("telemetry_overhead"),
        "resilience_overhead": cifar.get("resilience_overhead"),
        # compressed-exchange bytes + round time ({bytes_logical,
        # bytes_wire, ratio, round_s_plain, round_s_compressed}) measured
        # on real wire frames — the communication-efficiency PR metric
        "compression": cifar.get("compression"),
        # engine-level mixed-precision arms ({round_s_f32, round_s_bf16,
        # speedup, mfu_pct per arm, loss_delta}) — the roofline-path PR
        # metric; timing arms null on the CPU fallback
        "precision": cifar.get("precision"),
        # buffered-async cadence arms ({sync_round_vs_straggler,
        # async_cadence_vs, async_vs_clean_ratio, loss_delta, ...}) under
        # a fixed 2-of-8-clients-at-5x straggler FaultPlan — the
        # tail-independence PR metric (virtual-clock cadences always
        # measured; fit arms null on the CPU fallback)
        "async": cifar.get("async"),
        # durable checkpoint/resume ({write_ms_median, restore_ms,
        # frame_bytes, resume_overhead_ratio}) — the preemption-
        # survivability PR metric (host-I/O latencies always measured;
        # the resume-overhead fit arm null on the CPU fallback)
        "recovery": cifar.get("recovery"),
        # backend/device/version/git-rev facts tools/bench_gate.py
        # cross-checks against the metric name (a cpu_fallback number can
        # never masquerade as a TPU capture)
        "provenance": cifar["provenance"],
    }
    if "ops_overhead" in cifar:  # FL4HEALTH_BENCH_OPS=1
        # operations-plane fit() cost ({round_s_plain, round_s_ops_plane,
        # overhead_pct}) — tools/bench_gate.py bands overhead_pct
        record["ops_overhead"] = cifar["ops_overhead"]
    if fallback_note:
        record["note"] = fallback_note
    print(json.dumps(record))


def run_multichip_artifact() -> None:
    """``python bench.py --multichip``: one mesh-sharded fit() with full
    introspection, landed as ``MULTICHIP_<backend>_<ts>.json`` — per-chip
    steps/s, the ``fl_program_*`` reports (each carrying the mesh
    descriptor) and the run manifest. Runs on whatever devices are visible
    and raises when fewer than two are: a virtual mesh is something the
    caller asks for (``JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8``), never something
    this function moves to on its own."""
    import jax

    if len(jax.devices()) < 2:
        raise SystemExit(
            f"bench --multichip needs >= 2 devices, {len(jax.devices())} "
            f"visible on backend {jax.default_backend()!r}"
        )

    import tempfile

    from fl4health_tpu.observability import Observability
    from fl4health_tpu.parallel.program import MeshConfig

    devices = jax.devices()
    n_dev = len(devices)
    n_clients = mesh_cohort_size(n_dev)
    rounds = TIMED_ROUNDS
    out_dir = tempfile.mkdtemp(prefix="fl4h_multichip_")
    obs = Observability(enabled=True, introspection=True, telemetry=False,
                        output_dir=out_dir)
    _, sim = make_sim("cifar_cnn", conv_impl="mxu",
                      n_clients_override=n_clients, mesh=MeshConfig(),
                      observability=obs)
    t0 = time.perf_counter()
    sim.fit(rounds)
    wall = time.perf_counter() - t0
    # assert the deployed sharding, from the live state (the artifact's
    # claim is "the client axis ran split over n devices")
    leaf = jax.tree_util.tree_leaves(sim.client_states.params)[0]
    sharding_fact = {
        "spec": str(leaf.sharding.spec),
        "devices": len(leaf.sharding.device_set),
    }
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from perf_report import load_events

    events = load_events(os.path.join(out_dir, "metrics.jsonl"))
    round_events = sorted(events.get("round", []),
                          key=lambda r: r.get("round", 0))
    programs = events.get("program", [])
    manifest = {}
    mpath = os.path.join(out_dir, "manifest.json")
    if os.path.exists(mpath):
        with open(mpath) as f:
            manifest = json.load(f)
    steps_per_round = n_clients * LOCAL_STEPS
    per_chip = [r["steps_per_s_per_chip"] for r in round_events
                if "steps_per_s_per_chip" in r]
    platform, device_kind = _provenance()
    stamp = time.strftime("%Y%m%d_%H%M%S")
    record = {
        "metric": (f"fedavg_cifar_cnn_{n_clients}clients_mesh{n_dev}"
                   "_local_steps_per_sec_per_chip"),
        "value": (round(sum(per_chip) / len(per_chip), 2) if per_chip
                  else round(steps_per_round * rounds / wall / n_dev, 2)),
        # the two paths measure DIFFERENT things: per-round events exclude
        # compile wall, the fallback divides by total wall including the
        # one-time compile — name which one produced the headline number
        "value_definition": ("mean_per_round_exec" if per_chip
                             else "cohort_steps_over_total_wall_incl_compile"),
        "unit": "local_steps/sec/chip",
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": n_dev,
        "n_clients": n_clients,
        "rounds": rounds,
        "wall_s": round(wall, 3),
        "mesh": sim._program_builder.descriptor(),
        "client_stack_sharding": sharding_fact,
        "steps_per_s_per_chip_rounds": [round(v, 2) for v in per_chip],
        "execution_mode": sim._active_execution_mode,
        "program_introspection": {p["name"]: p for p in programs},
        "manifest": manifest,
        "data_provenance": "synthetic",
        "provenance": provenance_block(),
        "forced_host_devices": platform == "cpu",
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"MULTICHIP_{platform}{n_dev}_{stamp}.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"written": out_path, "value": record["value"],
                      "unit": record["unit"]}))


def run_precision_artifact() -> None:
    """``python bench.py --precision``: the mixed-precision A/B as its own
    artifact, landed as ``BENCH_precision_<label>_<ts>.json``. On a real
    accelerator the timing arms measure the bf16-vs-f32 round walls and
    per-arm MFU; on CPU the timing arms are skipped with the standard
    fallback annotation (bf16 is emulated on XLA:CPU) and the artifact
    still carries the measured ``loss_delta`` — the harness-health
    variant. FL4HEALTH_BENCH_PRECISION=1 forces the timing arms anywhere
    (e.g. to record the emulation tax explicitly)."""
    platform, device_kind = _provenance()
    fallback = platform == "cpu"
    timing = (os.environ.get("FL4HEALTH_BENCH_PRECISION") == "1"
              or not fallback)
    block = timed_precision_block(timing=timing)
    label = f"{platform}_fallback" if fallback else platform
    record = {
        "metric": (f"fedavg_cifar_cnn_{N_CLIENTS}clients_precision"
                   f"{'_cpu_fallback' if fallback else ''}"),
        "platform": platform,
        "device_kind": device_kind,
        "data_provenance": "synthetic",
        "provenance": provenance_block(),
        "model_dtype": "float32",
        "precision": block,
    }
    if fallback and not timing:
        record["note"] = (
            "CPU-fallback context: bf16 is emulated on XLA:CPU, so the "
            "round_s/mfu timing arms are skipped (null) — loss_delta is "
            "the measured half here. This artifact certifies the harness "
            "runs, not the speed claim; re-run on TPU for the speedup."
        )
    stamp = time.strftime("%Y%m%d_%H%M%S")
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"BENCH_precision_{label}_{stamp}.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"written": out_path,
                      "loss_delta": block["loss_delta"],
                      "speedup": block["speedup"]}))


def run_async_artifact() -> None:
    """``python bench.py --async``: the buffered-async sync-vs-async
    comparison as its own artifact, landed as
    ``BENCH_async_<label>_<ts>.json``. The virtual-clock cadence numbers
    (the headline: tail-independent round cadence) are exact on any
    backend; the fit loss/wall arms run everywhere too — they are small
    8-client MLP fits — unless FL4HEALTH_BENCH_ASYNC=0cpu-style gating is
    wanted, in which case use the in-record block instead."""
    platform, device_kind = _provenance()
    fallback = platform == "cpu"
    block = timed_async_block(timing=True)
    label = f"{platform}_fallback" if fallback else platform
    record = {
        "metric": (f"fedbuff_async_vs_sync_cadence"
                   f"{'_cpu_fallback' if fallback else ''}"),
        "platform": platform,
        "device_kind": device_kind,
        "data_provenance": "synthetic",
        "provenance": provenance_block(),
        "async": block,
    }
    if fallback:
        record["note"] = (
            "Cadence numbers are VIRTUAL-clock (deterministic compute-time "
            "model) and exact on any backend; the round_s_* chip walls are "
            "CPU-fallback harness health, not speed claims."
        )
    stamp = time.strftime("%Y%m%d_%H%M%S")
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"BENCH_async_{label}_{stamp}.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "written": out_path,
        "sync_degradation": block["sync_degradation"],
        "async_vs_clean_ratio": block["async_vs_clean_ratio"],
        "loss_delta": block["loss_delta"],
    }))


def run_sweep_artifact() -> None:
    """``python bench.py --sweep``: the shared-compilation scenario-grid
    measurement as its own artifact, landed as
    ``BENCH_sweep_<label>_<ts>.json``. The compile-amortization numbers
    ({cells, programs_compiled, cells_per_compile, compile_s_total}) are
    exact on any backend and are THE claim; on the CPU fallback the
    throughput fields are nulled with the standard annotation.
    FL4HEALTH_BENCH_SWEEP=1 forces the timing fields anywhere."""
    platform, device_kind = _provenance()
    fallback = platform == "cpu"
    timing = (os.environ.get("FL4HEALTH_BENCH_SWEEP") == "1"
              or not fallback)
    block = timed_sweep_block(timing=timing)
    label = f"{platform}_fallback" if fallback else platform
    record = {
        "metric": (f"scenario_sweep_shared_compilation"
                   f"{'_cpu_fallback' if fallback else ''}"),
        "platform": platform,
        "device_kind": device_kind,
        "data_provenance": "synthetic",
        "provenance": provenance_block(),
        "sweep": block,
    }
    if fallback and not timing:
        record["note"] = (
            "Compile-amortization counts (cells, programs_compiled, "
            "cells_per_compile) are exact on any backend and are the "
            "measured claim; XLA:CPU throughput fields are nulled — "
            "harness health, not speed. Re-run on TPU for steps/s."
        )
    stamp = time.strftime("%Y%m%d_%H%M%S")
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"BENCH_sweep_{label}_{stamp}.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "written": out_path,
        "cells": block["cells"],
        "programs_compiled": block["programs_compiled"],
        "cells_per_compile": block["cells_per_compile"],
    }))


def run_cohort_artifact() -> None:
    """``python bench.py --cohort``: the cohort-slot registry-scaling
    measurement as its own artifact, landed as
    ``BENCH_cohort_<label>_<ts>.json``. The O(K) program-identity facts
    (flops/peak-HBM equal across registry sizes at fixed K) are exact on
    any backend and are THE claim; on the CPU fallback the wall-flatness
    and staging-overlap ratios are nulled with the standard annotation.
    FL4HEALTH_BENCH_COHORT=1 forces the timing fields anywhere."""
    platform, device_kind = _provenance()
    fallback = platform == "cpu"
    timing = (os.environ.get("FL4HEALTH_BENCH_COHORT") == "1"
              or not fallback)
    block = timed_cohort_block(timing=timing)
    label = f"{platform}_fallback" if fallback else platform
    record = {
        "metric": (f"cohort_slot_registry_scaling"
                   f"{'_cpu_fallback' if fallback else ''}"),
        "platform": platform,
        "device_kind": device_kind,
        "data_provenance": "synthetic",
        "provenance": provenance_block(),
        "cohort": block,
    }
    if os.environ.get("FL4HEALTH_BENCH_COHORT_CHUNK") == "1":
        # opt-in chunked-dispatch arm (PR 17): dispatch/compile counts and
        # the measured host-roundtrip counter are exact on any backend;
        # only the wall numbers are timing-gated like everything else
        record["cohort_chunked"] = timed_cohort_chunk_block(timing=timing)
    if fallback:
        record["note"] = (
            "Program-identity facts (flops/peak-HBM equal across registry "
            "sizes at fixed K) are exact on any backend and are the "
            "measured claim. round_time_ratio_maxN_vs_minN is a SAME-BOX "
            "relative wall ratio — XLA:CPU harness health, not a TPU "
            "speed claim; the staging-overlap ratio is nulled (a CPU "
            "round is too small to hide host staging behind). Re-run on "
            "TPU for the overlap claim."
        )
    stamp = time.strftime("%Y%m%d_%H%M%S")
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"BENCH_cohort_{label}_{stamp}.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "written": out_path,
        "program_flops_identical": block["program_flops_identical"],
        "program_peak_hbm_identical": block["program_peak_hbm_identical"],
        "round_time_ratio_maxN_vs_minN": block[
            "round_time_ratio_maxN_vs_minN"],
    }))


def main() -> None:
    """Parent orchestrator: run each measurement in its own child. The
    parent never touches a JAX backend (the chip belongs to the child), and
    there is no retry on another backend: a child that finds no TPU, fails
    or times out ends the run non-zero."""
    if os.environ.get("FL4HEALTH_BENCH_CHILD"):
        run_measurement()
        return

    def attempt(force_cpu: bool, timeout_s: int, only: str | None = None,
                extra_env: dict | None = None) -> str | None:
        env = dict(os.environ)
        env["FL4HEALTH_BENCH_CHILD"] = "1"
        if force_cpu:
            env["FL4HEALTH_BENCH_FORCE_CPU"] = "1"
        if only:
            env["FL4HEALTH_BENCH_ONLY"] = only
        if extra_env:
            env.update(extra_env)
        try:
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True,
                text=True,
                timeout=timeout_s,
            )
        except subprocess.TimeoutExpired:
            print(
                f"bench child timed out after {timeout_s}s "
                f"(force_cpu={force_cpu})",
                file=sys.stderr,
            )
            return None
        for line in res.stdout.splitlines():
            if line.startswith("{"):
                return line
        print(
            f"bench child failed rc={res.returncode} (force_cpu={force_cpu}):\n"
            f"{res.stderr[-2000:]}",
            file=sys.stderr,
        )
        return None

    # Budget split: headline 45%, transformer 30%, the optional extras
    # whatever is left. Each config runs in its own child so a slow BERT
    # compile can never starve the headline number.
    forced_cpu = bool(os.environ.get("FL4HEALTH_BENCH_FORCE_CPU"))
    t_start = time.monotonic()
    # The explicit CPU harness switch shrinks every knob the operator
    # didn't pin: the full 64-client config does not fit a CPU budget
    # (XLA:CPU grouped convs). The metric name carries the actual client
    # count and the _cpu_fallback suffix.
    shrink: dict[str, str] = {
        k: v for k, v in (
            ("FL4HEALTH_BENCH_CLIENTS", "4"),
            ("FL4HEALTH_BENCH_ROUNDS", "2"),
            ("FL4HEALTH_BENCH_EAGER_CLIENTS", "2"),
        ) if forced_cpu and k not in os.environ
    }
    line = attempt(
        force_cpu=forced_cpu,
        timeout_s=(CHILD_TIMEOUT_S - 30 if forced_cpu
                   else int(CHILD_TIMEOUT_S * 0.45)),
        extra_env=shrink,
    )
    if line is None:
        raise SystemExit(
            "bench: the measurement child produced no record (no TPU, a "
            "failure or a timeout — see stderr above)"
        )
    record = json.loads(line)

    if os.environ.get("FL4HEALTH_BENCH_ONLY"):
        # Operator pinned a single config: the headline child already ran it
        # (the env propagates), its record may lack the headline keys
        # ("metric"/"value"), and every extra below would either duplicate
        # the measurement or KeyError after it. Print what was measured.
        print(json.dumps(record))
        return

    # Transformer (MFU-capable workload): own child + budget, optional.
    # Skipped on the explicit CPU harness run — unless the operator also
    # set FL4HEALTH_BENCH_TRANSFORMER=1 to force it there.
    want_tf = os.environ.get("FL4HEALTH_BENCH_TRANSFORMER", "1")
    explicit_tf = "FL4HEALTH_BENCH_TRANSFORMER" in os.environ
    if want_tf == "1" and (not forced_cpu or explicit_tf):
        # On the CPU harness run the transformer child inherits the same
        # shrunken knobs as the headline child — full size would just burn
        # its budget on XLA:CPU.
        tf_line = attempt(force_cpu=forced_cpu,
                          timeout_s=int(CHILD_TIMEOUT_S * 0.3),
                          only="transformer",
                          extra_env=shrink if forced_cpu else None)
        if tf_line is not None:
            record["transformer"] = json.loads(tf_line)
        else:
            record["transformer"] = {"skipped": "transformer child failed/timed out"}

    # Conv-impl A/B on real TPU (self-deciding: whether grouped convs or
    # im2col wins on the MXU gets answered by the artifact itself).
    # Skipped on the CPU harness run — the answer there is known (lax
    # wins, see make_sim) and the budget is tight. The A/B only spends
    # whatever the cifar/transformer children left UNUSED of the total
    # budget (they rarely exhaust their slices), so worst-case wall time
    # stays within CHILD_TIMEOUT_S — the headline record must never be lost
    # to an optional extra.
    ab_budget = int(CHILD_TIMEOUT_S - (time.monotonic() - t_start)) - 30
    if (not forced_cpu and ab_budget >= 120
            and "FL4HEALTH_BENCH_CONV" not in os.environ
            and os.environ.get("FL4HEALTH_BENCH_CONV_AB", "1") == "1"):
        alt_line = attempt(
            force_cpu=False, timeout_s=ab_budget,
            only="cifar_noeager", extra_env={"FL4HEALTH_BENCH_CONV": "mxu"},
        )
        if alt_line is not None:
            record["conv_mxu_alt"] = json.loads(alt_line)
            alt_sps = record["conv_mxu_alt"].get("steps_per_sec_per_chip", 0)
            if alt_sps and alt_sps > record["value"]:
                record["note_conv"] = (
                    f"mxu conv_impl measured FASTER ({alt_sps} vs "
                    f"{record['value']} steps/s) — flip the default "
                    "(FL4HEALTH_BENCH_CONV) next round"
                )

    # Long-context config (seq 2048 through the Pallas flash-attention
    # kernel) — TPU-only, with whatever budget remains after everything
    # else; first real-hardware datapoint for the long-context story.
    lc_budget = int(CHILD_TIMEOUT_S - (time.monotonic() - t_start)) - 30
    if (not forced_cpu
            and os.environ.get("FL4HEALTH_BENCH_LONGCTX", "1") == "1"):
        if lc_budget >= 240:
            lc_line = attempt(force_cpu=False, timeout_s=lc_budget,
                              only="transformer_long")
            # A failed datapoint must be visible in the artifact (same
            # contract as the transformer sibling), not indistinguishable
            # from the config being disabled.
            record["transformer_long"] = (
                json.loads(lc_line) if lc_line is not None
                else {"skipped": f"long-context child failed/timed out "
                                 f"(budget {lc_budget}s)"}
            )
        else:
            record["transformer_long"] = {
                "skipped": f"insufficient leftover budget ({lc_budget}s) — "
                "raise FL4HEALTH_BENCH_TIMEOUT_S to capture the "
                "long-context datapoint"
            }
    print(json.dumps(record))


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        run_multichip_artifact()
    elif "--precision" in sys.argv:
        run_precision_artifact()
    elif "--async" in sys.argv:
        run_async_artifact()
    elif "--sweep" in sys.argv:
        run_sweep_artifact()
    elif "--cohort" in sys.argv:
        run_cohort_artifact()
    else:
        main()
