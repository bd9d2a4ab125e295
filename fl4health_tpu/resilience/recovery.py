"""Crash-drill harness — prove preemption is a detour, not a restart.

The recovery subsystem's claims (versioned CRC-footed checkpoint frames,
retention-ring fallback, chunk-boundary and async snapshots — see
``checkpointing/state.py`` and ``docs/module_guides/recovery.md``) are only
worth shipping if a killed-and-resumed run PROVABLY reproduces the
uninterrupted trajectory. This module is the proof machinery, the same
pinned-claim discipline the resilience subsystem set for Byzantine faults
(``tests/resilience/test_faults.py::TestRobustnessClaim``):

1. ``run_child`` launches ``fit()`` in a REAL subprocess (its own JAX
   runtime, its own file handles — nothing shared with the test process);
2. a :class:`KillPoint` arms a deterministic SIGKILL inside the child —
   after round ``r``'s checkpoint publishes (``phase="post_save"``), or
   ``byte_offset`` bytes into the checkpoint write itself
   (``phase="mid_write"``, the torn-write drill). ``os.kill(getpid(),
   SIGKILL)`` is a true SIGKILL: no atexit, no flushing, no __del__ — the
   fidelity a preemptible-pool eviction has;
3. a second child resumes from the surviving checkpoint directory and
   writes its final params (serialized bytes) + per-round loss trajectory;
4. the drill compares those artifacts BYTE-identically against an
   uninterrupted run's.

``corrupt_newest_generation`` damages the newest ring generation between
kill and resume (truncation or byte-flip), driving the CRC-detect →
fallback-to-previous-generation path end-to-end.

Child protocol: ``python -m fl4health_tpu.resilience.recovery spec.json``
where the spec names a factory ``factory_file``/``factory_name`` —
``factory(ckpt_dir: str | None) -> FederatedSimulation`` — so the drill
composes with any configuration (execution modes, async_config, fault
plans) a test can express as a factory function.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import signal
import subprocess
import sys
from typing import Any

_DONE = "done.json"
_PARAMS = "final_params.msgpack"
_HISTORY = "history.json"


@dataclasses.dataclass(frozen=True)
class KillPoint:
    """Where (and how) the child kills itself.

    ``round``: the checkpoint save (by its ``round``/event meta) that arms
    the kill. ``phase="post_save"`` kills right after that save's atomic
    publish returns — the canonical "preempted between rounds" drill.
    ``phase="mid_write"`` kills ``byte_offset`` bytes into that save's
    file write — the torn-write drill: the temp file dies mid-body and the
    previously published generation must survive untouched.

    ``phase="registry_scatter"`` kills at the moment round ``round``'s
    cohort-slot rows would scatter back into the host registry
    (``ClientRegistry.scatter`` on the RoundConsumer thread) — the
    read-after-write edge of the gather/scatter cycle: the round's
    checkpoint (which runs AFTER the scatter in the epilogue) never
    publishes, the scatter gate never releases, and a resume must restore
    the previous generation's registry rows bit-identically.

    ``signal_name`` selects the delivery: ``"SIGKILL"`` (default — no
    atexit, no flushing, eviction fidelity) or ``"SIGTERM"`` — the
    graceful-preemption drill: ``fit()``'s trap converts it into a
    :class:`~fl4health_tpu.observability.flightrec.SigtermShutdown`, the
    flight recorder publishes a postmortem bundle naming the kill round,
    and the child exits 143 (``mid_write``/``registry_scatter`` stay
    SIGKILL-only: a handler running mid-torn-write or mid-scatter would
    let graceful teardown finish the very work the drill interrupts)."""

    round: int
    phase: str = "post_save"
    byte_offset: int = 64
    signal_name: str = "SIGKILL"

    def __post_init__(self):
        if self.phase not in ("post_save", "mid_write", "registry_scatter"):
            raise ValueError(
                "phase must be 'post_save', 'mid_write' or "
                f"'registry_scatter'; got {self.phase!r}"
            )
        if self.round < 1:
            raise ValueError(f"round must be >= 1; got {self.round}")
        if self.byte_offset < 1:
            raise ValueError(
                f"byte_offset must be >= 1; got {self.byte_offset}"
            )
        if self.signal_name not in ("SIGKILL", "SIGTERM"):
            raise ValueError(
                f"signal_name must be 'SIGKILL' or 'SIGTERM'; "
                f"got {self.signal_name!r}"
            )
        if (self.phase in ("mid_write", "registry_scatter")
                and self.signal_name != "SIGKILL"):
            raise ValueError(f"{self.phase} drills are SIGKILL-only")

    @property
    def signum(self) -> int:
        return getattr(signal, self.signal_name)


@dataclasses.dataclass
class DrillResult:
    """One child run's artifacts (present only when it exited cleanly)."""

    returncode: int
    params_bytes: bytes | None
    history: list[dict] | None
    stdout: str
    stderr: str

    @property
    def sigkilled(self) -> bool:
        return self.returncode == -signal.SIGKILL


# -- child side --------------------------------------------------------------

class _KillingFile:
    """File proxy that SIGKILLs the process after ``byte_offset`` bytes —
    flushed first, so the torn prefix really is on disk when we die."""

    def __init__(self, f, byte_offset: int):
        self._f = f
        self._remaining = byte_offset

    def write(self, data):
        if len(data) >= self._remaining:
            self._f.write(data[:self._remaining])
            self._f.flush()
            os.fsync(self._f.fileno())
            os.kill(os.getpid(), signal.SIGKILL)
        self._remaining -= len(data)
        return self._f.write(data)

    def __getattr__(self, name):
        return getattr(self._f, name)


def install_kill_hook(checkpointer, kill: KillPoint) -> None:
    """Wrap ``checkpointer.save`` so the configured save dies at the
    configured point. Works wherever the save runs (the async writer
    thread included — SIGKILL takes the whole process)."""
    import contextlib

    from fl4health_tpu.checkpointing import state as state_mod

    orig_save = checkpointer.save
    _orig_atomic_write = state_mod.atomic_write

    @contextlib.contextmanager
    def killing_atomic_write(path, mode="w"):
        with _orig_atomic_write(path, mode) as f:
            yield _KillingFile(f, kill.byte_offset)

    def save(trees, host=None, snapshotters=None, extra_meta=None):
        rnd = (extra_meta or {}).get("round")
        if rnd != kill.round:
            return orig_save(trees, host=host, snapshotters=snapshotters,
                             extra_meta=extra_meta)
        if kill.phase == "mid_write":
            state_mod.atomic_write = killing_atomic_write
            try:
                return orig_save(trees, host=host, snapshotters=snapshotters,
                                 extra_meta=extra_meta)
            finally:  # unreachable when the kill fires; kept for tiny frames
                state_mod.atomic_write = _orig_atomic_write
        out = orig_save(trees, host=host, snapshotters=snapshotters,
                        extra_meta=extra_meta)
        # SIGKILL dies here; SIGTERM raises SigtermShutdown in the MAIN
        # thread (this save may run on the async-writer thread) — the
        # fit() loop then dumps its postmortem bundle and exits 143
        os.kill(os.getpid(), kill.signum)
        return out

    checkpointer.save = save
    if kill.signal_name != "SIGTERM":
        return
    # A SIGTERM bundle names the round the run was at when the signal
    # arrived. A driver that hands its snapshots to the async writer (the
    # chunked one) runs on while the writer thread saves and signals, and
    # how far it gets is a race; so the thread that asks for the kill
    # round's snapshot waits for it, and takes the signal there.
    orig_snapshot = checkpointer.save_simulation_snapshot

    def save_simulation_snapshot(trees, current_round, *args, writer=None,
                                 **kwargs):
        orig_snapshot(trees, current_round, *args, writer=writer, **kwargs)
        if current_round == kill.round and writer is not None:
            writer.flush()

    checkpointer.save_simulation_snapshot = save_simulation_snapshot


def install_scatter_kill_hook(sim, kill: KillPoint) -> None:
    """Arm a ``phase="registry_scatter"`` kill: wrap the cohort-slot
    registry's ``scatter`` so the ``kill.round``-th scatter of the run
    SIGKILLs the process at entry — mid-epilogue, BEFORE that round's rows
    persist, before its checkpoint publishes, and before the producer's
    scatter gate releases. The drill then proves the resume restores the
    PREVIOUS generation's registry rows bit-identically (the PR 13
    gather-gated read-after-write edge)."""
    if kill.phase != "registry_scatter":
        raise ValueError(
            f"install_scatter_kill_hook needs phase='registry_scatter'; "
            f"got {kill.phase!r}"
        )
    registry = getattr(sim, "registry", None)
    if registry is None:
        raise RuntimeError(
            "a registry_scatter KillPoint needs cohort-slot execution "
            "(FederatedSimulation(cohort=CohortConfig(...)))"
        )
    orig_scatter = registry.scatter
    calls = {"n": 0}

    def scatter(idx, valid, client_rows, strategy_rows=None):
        calls["n"] += 1
        if calls["n"] == kill.round:
            os.kill(os.getpid(), signal.SIGKILL)
        return orig_scatter(idx, valid, client_rows, strategy_rows)

    registry.scatter = scatter


def _load_factory(factory_file: str, factory_name: str):
    spec = importlib.util.spec_from_file_location("_fl4h_drill_factory",
                                                  factory_file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, factory_name)


def child_main(spec_path: str) -> int:
    """Entry point of the drill subprocess: build the sim from the spec's
    factory, arm the kill point, fit, dump artifacts."""
    with open(spec_path) as f:
        spec = json.load(f)
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # match the test environment's 8-device virtual CPU platform so
        # parent-process and drill-child trajectories share one layout
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    from fl4health_tpu.utils.runtime import configure_compile_cache

    # the spec's directory is the default (the tests share .jax_test_cache
    # with the parent); an operator-set JAX_COMPILATION_CACHE_DIR wins
    configure_compile_cache(spec.get("jax_cache_dir"),
                            min_compile_time_secs=0.0)

    factory = _load_factory(spec["factory_file"], spec["factory_name"])
    sim = factory(spec.get("ckpt_dir"))
    kill = spec.get("kill")
    if kill:
        kp = KillPoint(**kill)
        if kp.phase == "registry_scatter":
            install_scatter_kill_hook(sim, kp)
        else:
            if sim.state_checkpointer is None:
                raise RuntimeError("a KillPoint needs a state_checkpointer")
            install_kill_hook(sim.state_checkpointer, kp)
    history = sim.fit(int(spec["n_rounds"]))

    from flax import serialization

    from fl4health_tpu.core.io import atomic_write

    out_dir = spec["out_dir"]
    os.makedirs(out_dir, exist_ok=True)
    params = jax.device_get(sim.global_params)
    with atomic_write(os.path.join(out_dir, _PARAMS), "wb") as f:
        f.write(serialization.to_bytes(params))
    rows = [
        {
            "round": rec.round,
            "fit_loss": rec.fit_losses.get("backward"),
            "eval_loss": rec.eval_losses.get("checkpoint"),
        }
        for rec in history
    ]
    with atomic_write(os.path.join(out_dir, _HISTORY)) as f:
        json.dump(rows, f)
    with atomic_write(os.path.join(out_dir, _DONE)) as f:
        json.dump({"rounds": len(history)}, f)
    return 0


# -- parent side -------------------------------------------------------------

def run_child(spec: dict[str, Any], spec_path: str,
              timeout_s: float = 600.0) -> DrillResult:
    """Write the spec and run one drill child; returns its artifacts (None
    where the child died before writing them — the killed arm)."""
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fl4health_tpu.resilience.recovery",
         spec_path],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
    )
    out_dir = spec["out_dir"]
    params = history = None
    if os.path.exists(os.path.join(out_dir, _DONE)):
        with open(os.path.join(out_dir, _PARAMS), "rb") as f:
            params = f.read()
        with open(os.path.join(out_dir, _HISTORY)) as f:
            history = json.load(f)
    return DrillResult(
        returncode=proc.returncode, params_bytes=params, history=history,
        stdout=proc.stdout, stderr=proc.stderr,
    )


def corrupt_newest_generation(ckpt_dir: str, name: str = "state", *,
                              mode: str = "truncate",
                              keep_bytes: int = 128) -> str:
    """Damage the newest ring generation on disk — the between-kill-and-
    resume corruption drill. ``mode="truncate"`` keeps only the first
    ``keep_bytes`` (a torn tail); ``mode="flip"`` XOR-flips one payload
    byte (at-rest corruption the CRC must catch). Returns the damaged
    path."""
    from fl4health_tpu.checkpointing.state import StateCheckpointer

    cands = StateCheckpointer(ckpt_dir, name).candidate_paths()
    if not cands:
        raise FileNotFoundError(f"no checkpoint generations in {ckpt_dir!r}")
    _gen, path = cands[0]
    with open(path, "rb") as f:
        data = f.read()
    if mode == "truncate":
        damaged = data[:keep_bytes]
    elif mode == "flip":
        i = len(data) // 2
        damaged = data[:i] + bytes([data[i] ^ 0xFF]) + data[i + 1:]
    else:
        raise ValueError(f"mode must be 'truncate' or 'flip'; got {mode!r}")
    with open(path, "wb") as f:
        f.write(damaged)
    return path


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1]))
