"""THE crash drill — subprocess fit() SIGKILLed at a seeded point, resumed
from the retention ring, pinned bit-identical to the uninterrupted run.

This is the acceptance proof of the preemption-survivable-federation PR
(the same pinned-claim discipline TestRobustnessClaim set for Byzantine
faults): a real subprocess, a real SIGKILL (no atexit, no flushing), a
real resume from disk, compared BYTE-identically (serialized final params
+ full loss trajectory) against an arm that was never interrupted.

Tier-1 lane (marker ``crash``): one post-save SIGKILL drill per sync
execution mode. The heavier matrix — mid-checkpoint-write kill,
corrupt-newest-generation fallback, buffered-async mid-plan resume — also
carries ``slow``.
"""

import os
import signal

import pytest

from fl4health_tpu.resilience.recovery import (
    corrupt_newest_generation,
    run_child,
)

FACTORY_FILE = os.path.join(os.path.dirname(__file__),
                            "recovery_factories.py")


def _repo_root():
    # tests live at <repo>/tests/resilience/
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _spec(tmp_path, tag, factory, n_rounds, ckpt_dir, kill=None):
    out_dir = str(tmp_path / f"{tag}_out")
    return {
        "factory_file": FACTORY_FILE,
        "factory_name": factory,
        "n_rounds": n_rounds,
        "ckpt_dir": str(ckpt_dir) if ckpt_dir is not None else None,
        "out_dir": out_dir,
        "kill": kill,
        "jax_cache_dir": os.path.join(_repo_root(), ".jax_test_cache"),
    }


def _run(tmp_path, tag, factory, n_rounds, ckpt_dir, kill=None):
    spec = _spec(tmp_path, tag, factory, n_rounds, ckpt_dir, kill)
    return run_child(spec, str(tmp_path / f"{tag}_spec.json"))


def _drill(tmp_path, factory, n_rounds=4, kill=None,
           damage_newest=None):
    """straight arm + killed arm + resumed arm; returns (straight,
    resumed). ``damage_newest`` optionally corrupts the newest surviving
    generation between kill and resume (the ring-fallback drill)."""
    straight = _run(tmp_path, "straight", factory, n_rounds,
                    tmp_path / "straight_ckpt")
    assert straight.returncode == 0, straight.stderr[-2000:]
    ckpt_dir = tmp_path / "drill_ckpt"
    killed = _run(tmp_path, "killed", factory, n_rounds, ckpt_dir,
                  kill=kill)
    assert killed.returncode == -signal.SIGKILL, (
        f"expected SIGKILL exit, got {killed.returncode}: "
        f"{killed.stderr[-2000:]}"
    )
    assert killed.params_bytes is None  # it really died before finishing
    if damage_newest is not None:
        corrupt_newest_generation(str(ckpt_dir), mode=damage_newest)
    resumed = _run(tmp_path, "resumed", factory, n_rounds, ckpt_dir)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    return straight, resumed


def _assert_bit_identical(straight, resumed, n_rounds):
    assert resumed.params_bytes == straight.params_bytes, (
        "resumed final params differ from the uninterrupted run"
    )
    assert resumed.history == straight.history
    assert [row["round"] for row in resumed.history] == list(
        range(1, n_rounds + 1)
    )


@pytest.mark.crash
@pytest.mark.parametrize("factory", ["sync_chunked", "sync_pipelined"])
def test_sigkill_after_round2_resumes_bit_identical(tmp_path, factory):
    """SIGKILL right after round 2's checkpoint publishes, on BOTH
    execution modes: the resumed run's final params and trajectory are
    byte-identical to the uninterrupted arm's."""
    straight, resumed = _drill(
        tmp_path, factory, n_rounds=4,
        kill={"round": 2, "phase": "post_save"},
    )
    _assert_bit_identical(straight, resumed, 4)


@pytest.mark.crash
@pytest.mark.slow
def test_sigkill_mid_checkpoint_write_leaves_previous_generation(tmp_path):
    """The torn-write drill: the kill lands mid-way through round 2's
    checkpoint WRITE. Atomic publish means the torn bytes die in the temp
    file; round 1's generation survives and the resume continues from it —
    bit-identical."""
    straight, resumed = _drill(
        tmp_path, "sync_chunked_every1", n_rounds=4,
        kill={"round": 2, "phase": "mid_write", "byte_offset": 200},
    )
    _assert_bit_identical(straight, resumed, 4)


@pytest.mark.crash
@pytest.mark.slow
@pytest.mark.parametrize("damage", ["truncate", "flip"])
def test_corrupt_newest_generation_falls_back_and_still_matches(
        tmp_path, damage):
    """Kill after round 2 (ring holds rounds 1 and 2), then damage the
    newest generation on disk. Restore must detect the corruption (CRC),
    fall back to round 1's generation, and STILL reproduce the
    uninterrupted trajectory."""
    straight, resumed = _drill(
        tmp_path, "sync_chunked_every1", n_rounds=3,
        kill={"round": 2, "phase": "post_save"},
        damage_newest=damage,
    )
    _assert_bit_identical(straight, resumed, 3)


def _assert_sigterm_bundle(tmp_path, killed, ckpt_dir, kill_round,
                           max_round=None):
    """Shared assertions of the SIGTERM-bundle drill: the child exited
    143, a COMPLETE bundle landed (CRC-valid ring frame, loadable
    trace.json, verdict naming the signal round), and tools/postmortem.py
    renders it with none of the dead process's state.

    ``max_round``: on the PIPELINED mode the producer/consumer legitimately
    run up to pipeline-depth rounds ahead of the checkpoint save that
    triggered the kill, so the signal can arrive with the run at a
    slightly later round — the verdict honestly names where the run WAS.
    The chunked mode records epilogues on the main thread (the thread the
    signal interrupts) and the kill hook makes that thread wait for the
    kill round's save, which the async writer does, so there the signal
    round is exact (``max_round=None``)."""
    import json
    import subprocess
    import sys as _sys

    from fl4health_tpu.observability.bundle import list_bundles, load_bundle
    from fl4health_tpu.observability.flightrec import SIGTERM_EXIT_CODE

    assert killed.returncode == SIGTERM_EXIT_CODE, (
        f"expected exit {SIGTERM_EXIT_CODE} (SIGTERM trap), got "
        f"{killed.returncode}: {killed.stderr[-2000:]}"
    )
    assert killed.params_bytes is None  # it really died before finishing
    bundles = list_bundles(str(ckpt_dir / "obs"))
    assert len(bundles) == 1, bundles
    bundle = load_bundle(bundles[0])  # ring frame is CRC-verified here
    verdict = bundle["verdict"]
    assert verdict["kind"] == "sigterm"
    assert verdict["signal"] == "SIGTERM"
    assert kill_round <= verdict["round"] <= (max_round or kill_round)
    # teardown drains may legitimately publish LATER checkpoints before
    # the dump — resume never points before the kill round
    assert verdict["resume"]["round"] >= kill_round
    assert bundle["ring"], "flight ring must hold the recorded rounds"
    assert any(e["round"] == kill_round for e in bundle["ring"])
    assert bundle["trace"]["traceEvents"], "trace.json must be loadable"
    assert any(e.get("event") == "round" for e in bundle["events"])
    # the incident report renders standalone (fresh interpreter, no state
    # from the dead child beyond the bundle directory)
    proc = subprocess.run(
        [_sys.executable,
         os.path.join(_repo_root(), "tools", "postmortem.py"),
         bundles[0], "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout)
    assert report["verdict"]["round"] == verdict["round"]
    assert report["resume_from"]["generation"] >= 1


@pytest.mark.crash
@pytest.mark.postmortem
def test_sigterm_mid_fit_publishes_postmortem_bundle(tmp_path):
    """THE SIGTERM-bundle drill (flight-recorder acceptance pin): a real
    subprocess fit() receives SIGTERM right after round 2's checkpoint
    publishes; the trap converts it into a bundle dump and a 143 exit, and
    the published bundle is complete and self-consistent — CRC-valid ring
    frame, loadable trace.json, verdict.json naming the kill round, and
    tools/postmortem.py renders it without the original process's state.
    Chunked mode: the driver thread records the epilogues, waits in the
    kill hook for the writer thread's save of the kill round and takes the
    signal there, so the signal round is exactly the kill round."""
    ckpt_dir = tmp_path / "drill_ckpt"
    killed = _run(
        tmp_path, "sigterm", "sync_chunked_flightrec", 4, ckpt_dir,
        kill={"round": 2, "phase": "post_save", "signal_name": "SIGTERM"},
    )
    _assert_sigterm_bundle(tmp_path, killed, ckpt_dir, kill_round=2)


@pytest.mark.crash
@pytest.mark.postmortem
@pytest.mark.slow
def test_sigterm_bundle_then_resume_matches_uninterrupted(tmp_path):
    """The full round trip on the PIPELINED mode: SIGTERM-with-bundle
    (signal round within pipeline depth of the kill save), then resume
    from a surviving checkpoint — bit-identical to the uninterrupted arm
    (the bundle never perturbs recovery)."""
    straight = _run(tmp_path, "straight", "sync_pipelined_flightrec", 4,
                    tmp_path / "straight_ckpt")
    assert straight.returncode == 0, straight.stderr[-2000:]
    ckpt_dir = tmp_path / "drill_ckpt"
    killed = _run(
        tmp_path, "killed", "sync_pipelined_flightrec", 4, ckpt_dir,
        kill={"round": 2, "phase": "post_save", "signal_name": "SIGTERM"},
    )
    # pipeline_depth=2 producer lookahead + the final round: the signal
    # may land with the run up to round 4
    _assert_sigterm_bundle(tmp_path, killed, ckpt_dir, kill_round=2,
                           max_round=4)
    resumed = _run(tmp_path, "resumed", "sync_pipelined_flightrec", 4,
                   ckpt_dir)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_bit_identical(straight, resumed, 4)


@pytest.mark.crash
@pytest.mark.slow
@pytest.mark.parametrize("factory", ["async_chunked", "async_pipelined"])
def test_async_sigkill_resumes_mid_plan_bit_identical(tmp_path, factory):
    """Buffered-async drill: the kill lands after event 2's snapshot (which
    persisted the pending buffer + event cursor + virtual clock); the
    resumed run continues the static event plan mid-flight and matches the
    uninterrupted arm byte-identically."""
    straight, resumed = _drill(
        tmp_path, factory, n_rounds=4,
        kill={"round": 2, "phase": "post_save"},
    )
    _assert_bit_identical(straight, resumed, 4)


def test_killpoint_registry_scatter_validation():
    """registry_scatter phase: SIGKILL-only (a handler mid-scatter would
    let graceful teardown finish the very work the drill interrupts), and
    the hook refuses non-cohort simulations."""
    from fl4health_tpu.resilience.recovery import (
        KillPoint,
        install_scatter_kill_hook,
    )

    KillPoint(round=2, phase="registry_scatter")  # valid
    with pytest.raises(ValueError, match="SIGKILL-only"):
        KillPoint(round=2, phase="registry_scatter",
                  signal_name="SIGTERM")

    class _NoRegistry:
        registry = None

    with pytest.raises(RuntimeError, match="cohort-slot"):
        install_scatter_kill_hook(
            _NoRegistry(), KillPoint(round=2, phase="registry_scatter")
        )
    with pytest.raises(ValueError, match="registry_scatter"):
        install_scatter_kill_hook(_NoRegistry(), KillPoint(round=2))


@pytest.mark.parametrize("signal_name,waits_at", [("SIGTERM", [2]),
                                                 ("SIGKILL", [])])
def test_sigterm_kill_hook_waits_for_the_kill_rounds_save(signal_name,
                                                          waits_at):
    """The chunked driver hands its snapshots to the writer thread and runs
    on. A SIGTERM drill names the round the run was at, so the thread that
    asks for the kill round's snapshot waits for the writer there, and
    nowhere else; a SIGKILL drill takes the process wherever it is."""
    from fl4health_tpu.resilience.recovery import KillPoint, install_kill_hook

    class Writer:
        def __init__(self):
            self.submitted, self.flushed_after = [], []

        def submit(self, fn, **kwargs):  # never runs the job: no signal here
            self.submitted.append(kwargs["extra_meta"]["round"])

        def flush(self):
            self.flushed_after.append(self.submitted[-1])

    class Checkpointer:
        def save(self, **kwargs):
            raise AssertionError("the writer never runs a job in this test")

        def save_simulation_snapshot(self, trees, current_round, n_clients,
                                     history, writer=None, fleet=None):
            writer.submit(self.save, extra_meta={"round": current_round})

    ckpt, writer = Checkpointer(), Writer()
    install_kill_hook(ckpt, KillPoint(round=2, signal_name=signal_name))
    for rnd in (1, 2, 3):
        ckpt.save_simulation_snapshot({}, rnd, 4, [], writer=writer)
    assert writer.submitted == [1, 2, 3]
    assert writer.flushed_after == waits_at


@pytest.mark.crash
@pytest.mark.bigcohort
@pytest.mark.slow
def test_sigkill_mid_registry_scatter_resumes_bit_identical(tmp_path):
    """The cohort kill-matrix drill (PR 13's gather-gated read-after-write
    edge): SIGKILL at the moment round 2's slot rows would scatter into
    the host registry — BEFORE that round's rows persist, before its
    cohort-kind checkpoint publishes. The resume restores round 1's
    generation (slot states + registry dirty rows) and reproduces the
    uninterrupted run byte-identically."""
    straight, resumed = _drill(
        tmp_path, "cohort_sampled", n_rounds=4,
        kill={"round": 2, "phase": "registry_scatter"},
    )
    _assert_bit_identical(straight, resumed, 4)


@pytest.mark.selfheal
@pytest.mark.crash
@pytest.mark.slow
def test_sigkill_of_supervised_process_resumes_self_healed(tmp_path):
    """THE supervised-process kill drill: the self-healing run (scale
    fault -> watchdog halt -> rollback -> quarantine -> resume) is
    SIGKILLed after round 7's checkpoint — after the recovery settled —
    and a fresh supervised process over the same checkpoint ring + ledger
    finishes the run BYTE-identically to a supervised arm that was never
    killed: the quarantine roster survived the eviction in the recovery
    ledger, the training state in the generation ring."""
    straight = _run(tmp_path, "straight", "supervised_selfheal", 10,
                    tmp_path / "straight_ckpt")
    assert straight.returncode == 0, straight.stderr[-2000:]
    ckpt_dir = tmp_path / "drill_ckpt"
    killed = _run(tmp_path, "killed", "supervised_selfheal", 10, ckpt_dir,
                  kill={"round": 7, "phase": "post_save"})
    assert killed.returncode == -signal.SIGKILL, (
        f"expected SIGKILL exit, got {killed.returncode}: "
        f"{killed.stderr[-2000:]}"
    )
    # the ledger survived the kill with the quarantine roster armed
    import json

    with open(ckpt_dir / "recovery_ledger.json") as f:
        ledger = json.load(f)
    assert sorted(int(c) for c in ledger["quarantine"]) == [1, 2]
    resumed = _run(tmp_path, "resumed", "supervised_selfheal", 10,
                   ckpt_dir)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_bit_identical(straight, resumed, 10)
