"""One run of one cell: set-up, the check calls, warm-up, the measured
window over ``FederatedSimulation.fit``, then the reference and the
comparison. Cell-agnostic: everything particular comes from the cell's
data files and from modules found by name."""

from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import threading
import time

from . import build, check
from .device import DeviceInfo, memory_peak_bytes
from .spec import Cell, load_module


class RoundClock:
    """The harness's own reporter: ``fit()`` calls ``report(payload,
    round=r)`` from its RoundConsumer once round r's results are on the
    host. Records when."""

    def __init__(self):
        self.stamps: list[float] = []
        self._lock = threading.Lock()

    def report(self, payload, round=None, **_):
        now = time.perf_counter()
        if round is not None:
            with self._lock:
                self.stamps.append(now)

    def shutdown(self):
        pass

    def take(self):
        with self._lock:
            out, self.stamps = self.stamps, []
        return out


class CompileCounter:
    """jax.monitoring listener: backend compiles (cache loads included)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.last_call = 1  # compiles of the newest fit() call
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += float(duration)


def configure_cache(root: str) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says). Every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".bench_cache", "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def global_leaf_norms(sim, w0_host: dict) -> dict:
    """Per-leaf ||global - w0|| of the simulation's own state, on the host."""
    import numpy as np

    flat = build.flatten(sim.global_params)
    out = {}
    for k, base in w0_host.items():
        cur = np.asarray(flat[k], np.float32)
        out[k] = float(np.sqrt(np.sum(np.square(
            cur.astype(np.float64) - base.astype(np.float64)))))
    return out


def first_rounds(cell: Cell, seed: int, clock=None, break_program=None,
                 compiles=None):
    """Build the simulation from the seed and drive it through the cell's
    check calls with ``fit()`` itself. Returns (sim, prog) where ``prog``
    holds what those calls produced: each round's fit loss and, after each
    call, the per-leaf norms of the global weights' change."""
    import numpy as np

    ref_mod, w0, data, rows = build.make_inputs(cell, seed)
    w0_host = {k: np.asarray(v) for k, v in w0.items()}
    sim = build.build_sim(cell, seed, w0, data, rows,
                          reporters=[clock] if clock is not None else [])
    del w0, data
    if break_program is not None:
        break_program(sim)
    prog = {"losses": [], "snapshots": []}
    for n in cell.job["check_calls"]:
        hist_before = len(sim.history)
        before = compiles.count if compiles is not None else 0
        history = sim.fit(int(n))
        if compiles is not None:
            compiles.last_call = compiles.count - before
        prog["losses"] += [float(r.fit_losses["backward"])
                           for r in history[hist_before:]]
        prog["snapshots"].append(global_leaf_norms(sim, w0_host))
    return sim, prog


def reference_rounds(cell: Cell, seed: int, numerics: str = "FLOAT32") -> dict:
    """The plain reference over the same seed's weights and data, following
    the cell's check calls. ``numerics`` names the precision policy:
    ``FLOAT32`` is the reference proper, a key of ``CONTROLS`` a control."""
    job = cell.job
    ref_mod, w0, data, rows = build.make_inputs(cell, seed)
    num = load_module("reference", "numerics", cell.bench_dir)
    nm = num.FLOAT32 if numerics == "FLOAT32" else num.CONTROLS[numerics]
    # the plain strategy, optimizer and objective are found by the traffic
    # file's names
    strategy = load_module("reference/strategies", cell.strategy["name"],
                           cell.bench_dir)
    opt_mod = load_module("reference/optimizers", cell.optimizer["name"],
                          cell.bench_dir)
    objective = load_module("reference/objectives", cell.objective["name"],
                            cell.bench_dir)
    return strategy.run(
        lambda p, x, nm_: ref_mod.forward(p, x, cell.cfg, job, nm_),
        w0, data[0], data[1], rows, loss=objective.loss,
        batch=int(job["batch"]),
        steps=int(job["local_steps"]), optimizer=(opt_mod, cell.optimizer),
        seed=build.datagen.seed31(seed), calls=job["check_calls"],
        client_block=int(job.get("reference_client_block", 1)), nm=nm,
        strategy=cell.strategy)


def release(sim) -> None:
    """Drop a simulation's device state."""
    sim.client_states = sim.server_state = None
    sim._x_train_stack = sim._y_train_stack = None
    sim._x_val_stack = sim._y_val_stack = None
    sim._val_cache = sim._sharded_banks_cache = None
    gc.collect()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             dev: DeviceInfo, t_process_start: float,
             break_program=None) -> dict:
    """Returns the result object. ``break_program(sim)`` is the tests' hook
    to break the timed path underneath before the first call."""
    import jax

    job = cell.job
    compiles = CompileCounter()
    clock = RoundClock()

    def phase(what):
        print(f"set-up: {what} at {time.perf_counter() - t_process_start:.1f} s "
              f"({compiles.count} compiles, {compiles.seconds:.1f} s)",
              file=sys.stderr)

    phase("backend up")
    # -- the first rounds, through the window's own call and feed ---------
    sim, prog = first_rounds(cell, seed, clock, break_program, compiles)
    phase("check calls done")
    # -- warm-up: the window's own call shape until a call compiles nothing.
    # The per-round driver's programs do not depend on the number of rounds,
    # so its check calls have already run every program of the window; the
    # chunked scan's length is part of its shape, so it is called once more.
    r_fit = int(job["rounds_per_fit"])
    mode = sim._active_execution_mode
    warm_calls = 0
    need_warm = compiles.last_call > 0 or (
        mode == "chunked_scan" and r_fit not in job["check_calls"])
    while need_warm and warm_calls < 3:
        before = compiles.count
        sim.fit(r_fit)
        warm_calls += 1
        need_warm = compiles.count > before
    phase(f"{warm_calls} warm-up calls done")
    clock.take()
    mode = sim._active_execution_mode
    if mode != job["expect_mode"]:
        raise RuntimeError(
            f"execution mode resolved to {mode!r}, the traffic file expects "
            f"{job['expect_mode']!r}")
    events = [e for e in sim.observability.registry.events
              if e.get("event") == "execution_mode"]
    if not events or events[-1].get("mode") != job["expect_mode"]:
        raise RuntimeError(f"execution_mode event says {events[-1:]!r}")
    gc.collect()

    # -- the measured window ----------------------------------------------
    window_s = float(seconds)
    trace_dir = None
    if trace:
        window_s = 0.0  # one call of the window's own shape
        trace_dir = os.path.join(cell.root, ".bench_cache", "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
    compiles_before = compiles.count
    setup_compile_s = compiles.seconds
    intervals, call_ms, rounds_done, failed = [], [], 0, 0
    t_start = time.perf_counter()
    setup_s = t_start - t_process_start
    while True:
        t_call = time.perf_counter()
        n_hist = len(sim.history)
        with jax.profiler.TraceAnnotation("bench_fit_call"):
            history = sim.fit(r_fit)
        t_done = time.perf_counter()
        call_ms.append((t_done - t_call) * 1e3)
        new = history[n_hist:]
        rounds_done += len(new)
        failed += sum(1 for r in new
                      if not math.isfinite(float(r.fit_losses["backward"])))
        prev = t_call
        for t in sorted(clock.take()):
            intervals.append((t - prev) * 1e3)
            prev = t
        if t_done - t_start >= window_s:
            break
    wall = t_done - t_start
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - compiles_before
    peak_bytes = memory_peak_bytes(dev.chips)

    # what every metric's own reader (end_to_end/<name>.py, and with the
    # trace beside it layer_metrics/<name>.py) reads
    ctx = {
        "cell": cell, "dev": dev, "chips": dev.chips, "mode": mode,
        "rounds": rounds_done, "wall_s": wall, "setup_s": setup_s,
        "steps": rounds_done * int(job["clients"]) * int(job["local_steps"]),
        "step_flops": load_module("flops", cell.family, cell.bench_dir
                                  ).train_step_flops(cell.cfg, job),
        "peak_bytes": peak_bytes, "round_intervals_ms": intervals,
        "call_ms": call_ms, "compile_s": setup_compile_s,
        "compiles_in_window": compiles_in_window,
    }

    device = dict(dev.public(), memory_peak_bytes=peak_bytes)
    result = {"correct": False, "attempted": rounds_done, "failed": failed,
              "metrics": {}, "device": device}

    if trace:
        from .trace import layer_readings

        readings, busy_s, traced_s, breakdown = layer_readings(
            cell, trace_dir, ctx)
        device["busy_s"], device["window_s"] = busy_s, traced_s
        result["breakdown"] = breakdown
        for m in cell.metrics("per_layer"):
            if readings.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": readings[m["name"]], "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.metrics("end_to_end"):
            value = load_module("end_to_end", m["name"], cell.bench_dir
                                ).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}

    # -- the reference, once the program's state is freed ------------------
    release(sim)
    del sim, history, new
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_rounds(cell, seed)
    ok, checks = check.decide(check.numbers(prog, ref), cell.limits())
    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    ok = ok and compiles_in_window == 0 and failed == 0
    result["correct"] = bool(ok)
    result["reference_s"] = time.perf_counter() - t_ref
    result["window_s"] = wall
    result["checks"] = checks  # last key: each number beside its limit
    print(f"first rounds' losses: program {prog['losses']} "
          f"reference {ref['losses']}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    return result
