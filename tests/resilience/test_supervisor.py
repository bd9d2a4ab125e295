"""Recovery supervisor — the self-healing ladder (resilience/supervisor.py).

Covers the RecoveryPolicy contract, ladder escalation/probation units over
a scripted fake simulation, the quarantine roster + ledger persistence,
per-rung mitigations against real simulations, armed-but-never-engaged
bit-identity on BOTH execution modes, and THE pinned drill: under a
probability-1 scale-fault plan, unsupervised FedAvg diverges and halts via
the watchdog while the supervised run rolls back, quarantines exactly the
flight-recorder-named suspects, resumes and converges within pinned
tolerance of the fault-free trajectory — one postmortem bundle per
attempt, ``/healthz`` restored after probation.
"""

import json
import os

import jax
import numpy as np
import optax
import pytest
from flax import serialization

from fl4health_tpu.checkpointing.state import (
    CheckpointCorruptError,
    SimulationStateCheckpointer,
)
from fl4health_tpu.clients import engine
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.observability import (
    HealthPolicy,
    HealthWatchdog,
    MetricsRegistry,
    Observability,
    SigtermShutdown,
    Tracer,
    TrainingHealthError,
)
from fl4health_tpu.observability.bundle import list_bundles, load_bundle
from fl4health_tpu.resilience import (
    ClientFault,
    FaultPlan,
    QuarantinePolicy,
    QuarantiningStrategy,
    QuorumControl,
    RecoveryPolicy,
    RecoverySupervisor,
    RobustFedAvg,
    rank_suspects,
)
from fl4health_tpu.server.simulation import (
    ClientDataset,
    ClientFailuresError,
    FailurePolicy,
    FederatedSimulation,
)
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.transport import QuorumError

N_CLASSES = 3
N_CLIENTS = 6
POISONED = (1, 2)

# probability-1 scale fault on two clients from round 2 on — the drill's
# persistent Byzantine pair (same attack family as TestRobustnessClaim)
SCALE_FAULT = FaultPlan(seed=3, client_faults=(
    ClientFault(clients=POISONED, kind="scale", scale=-15.0,
                probability=1.0, start_round=2),
))


def _datasets(n=N_CLIENTS, poison_nan=()):
    out = []
    for i in range(n):
        x, y = synthetic_classification(
            jax.random.PRNGKey(20 + i), 32, (6,), N_CLASSES
        )
        x = np.asarray(x).copy()
        if i in poison_nan:
            x[:] = np.nan
        out.append(ClientDataset(x[:24], y[:24], x[24:], y[24:]))
    return out


def make_obs(output_dir=None, watchdog=True):
    return Observability(
        enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
        sync_device=False,
        output_dir=str(output_dir) if output_dir else None,
        watchdog=HealthWatchdog(HealthPolicy(
            loss_divergence_window=1, loss_divergence_factor=1.4,
            on_loss_divergence="halt", on_nonfinite="halt",
        )) if watchdog else None,
    )


def make_sim(mode="chunked", *, ckpt_dir=None, fault=None, recovery=None,
             obs=None, datasets=None, strategy=None, n_rounds_ckpt=1,
             **kwargs):
    kw = dict(kwargs)
    if ckpt_dir is not None:
        kw["state_checkpointer"] = SimulationStateCheckpointer(
            str(ckpt_dir), checkpoint_every=n_rounds_ckpt, keep=8,
        )
    return FederatedSimulation(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(8,), n_outputs=N_CLASSES)),
            engine.masked_cross_entropy,
        ),
        tx=optax.sgd(0.05),
        strategy=strategy if strategy is not None else FedAvg(),
        datasets=datasets if datasets is not None else _datasets(),
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, local_epochs=None, seed=9,
        execution_mode=mode,
        observability=obs if obs is not None else Observability(
            enabled=False
        ),
        fault_plan=fault, recovery=recovery, **kw,
    )


def _params_bytes(sim) -> bytes:
    return serialization.to_bytes(jax.device_get(sim.global_params))


# ---------------------------------------------------------------------------
class TestRecoveryPolicy:
    def test_defaults_validate(self):
        p = RecoveryPolicy()
        assert p.rungs == ("retry", "quarantine", "robustify", "degrade")

    @pytest.mark.parametrize("kw", [
        {"rungs": ()},
        {"rungs": ("nope",)},
        {"rungs": ("retry", "retry")},
        {"recover_kinds": ("sigterm",)},
        {"attempts_per_rung": 0},
        {"max_total_attempts": 0},
        {"probation_rounds": 0},
        {"quarantine_rounds": -1},
        {"max_suspects": 0},
        {"quorum_relax": 0.0},
        {"cohort_shrink": 1.5},
        {"server_lr_factor": 0.0},
        {"robust_method": "nope"},
    ])
    def test_rejects_bad_values(self, kw):
        with pytest.raises(ValueError):
            RecoveryPolicy(**kw)

    def test_simulation_rejects_duck_typed_policy(self):
        with pytest.raises(TypeError, match="RecoveryPolicy"):
            make_sim(recovery={"rungs": ("retry",)})


# ---------------------------------------------------------------------------
class _FakeManager:
    def __init__(self, fraction=0.5, n_clients=8):
        self.fraction = fraction
        self.n_clients = n_clients


class _FakeSim:
    """Scripted stand-in exposing exactly the surface the supervisor
    drives; ``failures`` lists the exception each successive fit attempt
    raises (None = clean completion)."""

    def __init__(self, failures, strategy=None, manager=None,
                 checkpointer=None):
        self._failures = list(failures)
        self.observability = Observability(
            enabled=False, tracer=Tracer(), registry=MetricsRegistry()
        )
        self.state_checkpointer = checkpointer
        self.strategy = strategy if strategy is not None else FedAvg()
        self.client_manager = manager
        self._async_active = False
        self._cohort_active = False
        self.n_clients = 8
        self._fit_n_rounds = 4
        self.fits = 0
        self.resets = 0
        self.rebuilds = 0

    def _fit_unsupervised(self, n_rounds):
        self.fits += 1
        if self._failures:
            exc = self._failures.pop(0)
            if exc is not None:
                raise exc
        return "done"

    def _reset_to_initial(self):
        self.resets += 1

    def _build_compiled(self):
        self.rebuilds += 1


def _the(round_=2, clients=(3,)):
    return TrainingHealthError(
        "halt", round=round_, clients=list(clients), check="nonfinite"
    )


class TestLadderUnits:
    def test_escalates_through_every_rung_then_halts(self):
        sim = _FakeSim([_the()] * 5, manager=_FakeManager())
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(attempts_per_rung=1, probation_rounds=100),
            quorum_control=QuorumControl(quorum=3),
        )
        with pytest.raises(TrainingHealthError):
            sup.run(4)
        # retry, quarantine, robustify, degrade each got exactly one
        # attempt; the 5th failure exhausted the ladder and re-raised
        assert sup._attempts == {"retry": 1, "quarantine": 1,
                                 "robustify": 1, "degrade": 1}
        assert sim.fits == 5
        assert sim.resets == 4  # no checkpointer: every rollback restarts
        assert isinstance(sim.strategy, RobustFedAvg)  # robustify rung
        assert sim.rebuilds == 1
        assert sim.client_manager.fraction == pytest.approx(0.25)  # degrade
        assert sup.quorum_control.quorum == 2  # degrade relaxed the quorum
        assert sup.quarantined_ids(1) == [3]

    def test_recovers_then_succeeds(self):
        sim = _FakeSim([_the(), None])
        sup = RecoverySupervisor(sim, RecoveryPolicy())
        assert sup.run(4) == "done"
        assert sim.fits == 2
        assert sup._total_attempts == 1

    def test_quarantine_skipped_without_suspects(self):
        # a cohort-level verdict with no named clients and an empty ring:
        # the quarantine rung has nobody to mask — the ladder skips it
        sim = _FakeSim([_the(clients=()), _the(clients=())])
        sup = RecoverySupervisor(
            sim,
            RecoveryPolicy(rungs=("quarantine", "robustify"),
                           attempts_per_rung=1),
        )
        with pytest.raises(TrainingHealthError):
            sup.run(4)
        assert "quarantine" not in sup._attempts
        assert sup._attempts == {"robustify": 1}

    def test_nonrecoverable_kinds_propagate_untouched(self):
        for exc, raises in ((RuntimeError("boom"), RuntimeError),
                            (SigtermShutdown(), SystemExit)):
            sim = _FakeSim([exc])
            sup = RecoverySupervisor(sim, RecoveryPolicy())
            with pytest.raises(raises):
                sup.run(4)
            assert sup._total_attempts == 0
            assert sim.fits == 1

    def test_max_total_attempts_is_a_hard_ceiling(self):
        sim = _FakeSim([_the()] * 10)
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(attempts_per_rung=10, max_total_attempts=2)
        )
        with pytest.raises(TrainingHealthError):
            sup.run(4)
        assert sup._total_attempts == 2
        assert sim.fits == 3

    def test_quorum_error_is_recoverable(self):
        err = QuorumError("quorum lost", required=3, succeeded=1,
                          failures=[("h:1", "timeout")])
        sim = _FakeSim([err, None], manager=_FakeManager())
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(rungs=("degrade",)),
            quorum_control=QuorumControl(quorum=3),
        )
        assert sup.run(4) == "done"
        assert sup.quorum_control.quorum == 2

    def test_checkpoint_corrupt_clears_ring_and_restarts(self, tmp_path):
        sc = SimulationStateCheckpointer(str(tmp_path))
        bad = tmp_path / "state.g00000001.ckpt"
        bad.write_bytes(b"FL4HCKPT garbage")
        err = CheckpointCorruptError(str(bad), "CRC32 mismatch")
        sim = _FakeSim([err, None], checkpointer=sc)
        sup = RecoverySupervisor(sim, RecoveryPolicy(rungs=("retry",)))
        assert sup.run(4) == "done"
        assert not sc.exists()  # wreckage cleared
        assert sim.resets == 1  # nothing durable left: restart from init


# ---------------------------------------------------------------------------
class TestQuarantineRosterAndProbation:
    def test_keep_mask_and_release_round(self):
        sim = _FakeSim([])
        sup = RecoverySupervisor(sim, RecoveryPolicy(quarantine_rounds=3))
        assert sup.keep_mask(1, 6) is None  # never engaged: pure fast path
        sup._apply_quarantine([1, 4], resume_round=5)
        keep = sup.keep_mask(5, 6)
        np.testing.assert_array_equal(keep, [1, 0, 1, 1, 0, 1])
        assert sup.quarantined_ids(7) == [1, 4]
        # release at resume_round + quarantine_rounds = 8
        assert sup.keep_mask(8, 6) is None
        assert sup.quarantined_ids(8) == []

    def test_quarantine_rounds_zero_is_rest_of_run(self):
        sup = RecoverySupervisor(
            _FakeSim([]), RecoveryPolicy(quarantine_rounds=0)
        )
        sup._apply_quarantine([2], resume_round=1)
        assert sup.quarantined_ids(10_000) == [2]

    def test_probation_resets_ladder_and_marks_healthy(self):
        sim = _FakeSim([])
        obs = sim.observability
        obs.enabled = True  # metrics/healthz surface for this unit
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(probation_rounds=2, attempts_per_rung=3)
        )
        sup._attempts = {"retry": 2}
        sup._rung_idx = 1
        sup._engaged = True
        sup._probation_after = 4  # the failure was at round 4
        obs.mark_unhealthy("recovering")
        sup.note_round(3)  # replayed pre-failure round: no credit
        sup.note_round(4)
        assert sup._healthy_rounds == 0
        sup.note_round(5)
        assert sup._engaged and obs.unhealthy_reason is not None
        sup.note_round(6)  # second healthy round PAST the failure:
        # probation passes
        assert not sup._engaged
        assert sup._attempts == {} and sup._rung_idx == 0
        assert obs.unhealthy_reason is None  # mark_healthy: /healthz 200
        snap = obs.registry.snapshot()
        assert snap["fl_recovery_engaged"] == 0.0
        assert snap["fl_recovery_probations_passed_total"] == 1.0

    def test_ledger_survives_a_new_process(self, tmp_path):
        path = str(tmp_path / "recovery_ledger.json")
        sim = _FakeSim([_the(clients=(2,)), None])
        sup = RecoverySupervisor(
            sim,
            RecoveryPolicy(rungs=("quarantine",), quarantine_rounds=0),
            ledger_path=path,
        )
        assert sup.run(4) == "done"
        assert sup.quarantined_ids(1) == [2]
        with open(path) as f:
            doc = json.load(f)
        assert doc["quarantine"] == {"2": 0}
        # "new process": a fresh supervisor over the same ledger path
        sup2 = RecoverySupervisor(
            _FakeSim([]), RecoveryPolicy(rungs=("quarantine",)),
            ledger_path=path,
        )
        assert sup2.quarantined_ids(1) == [2]
        assert sup2._engaged and sup2._total_attempts == 1

    def test_ledger_rearms_robustify_and_degrade_mitigations(
            self, tmp_path):
        """A SIGKILLed process's factory rebuilds the sim with its
        ORIGINAL strategy/manager/quorum — the ledger must re-apply the
        journaled robustify swap and degrade relaxations, not just
        remember their spent attempt budgets."""
        from fl4health_tpu.server.client_manager import FixedFractionManager

        path = str(tmp_path / "recovery_ledger.json")
        sim = _FakeSim([_the(), _the(), None],
                       manager=FixedFractionManager(8, 0.5))
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(rungs=("robustify", "degrade")),
            ledger_path=path, quorum_control=QuorumControl(quorum=3),
        )
        assert sup.run(4) == "done"
        assert isinstance(sim.strategy, RobustFedAvg)
        assert sim.client_manager.k == 2
        # "new process": plain FedAvg + original manager/quorum again
        sim2 = _FakeSim([], manager=FixedFractionManager(8, 0.5))
        ctl2 = QuorumControl(quorum=3)
        RecoverySupervisor(sim2, RecoveryPolicy(), ledger_path=path,
                           quorum_control=ctl2)
        assert isinstance(sim2.strategy, RobustFedAvg)
        assert sim2.strategy.trim_fraction == pytest.approx(0.2)
        assert sim2.rebuilds == 1  # the swap re-traced the programs
        assert sim2.client_manager.fraction == pytest.approx(0.25)
        assert sim2.client_manager.k == 2
        assert ctl2.quorum == 2

    def test_robustify_rung_skipped_when_nothing_to_tighten(self):
        """An existing RobustFedAvg with no trimming knob (median/Krum)
        leaves the rung inapplicable — no parameter-identical copy, no
        wasted re-trace, no burned attempt."""
        sim = _FakeSim([], strategy=RobustFedAvg(method="median"))
        sup = RecoverySupervisor(sim, RecoveryPolicy())
        assert sup._robustify_target() is None
        assert not sup._rung_applicable("robustify", [1])
        # ledger restore still reaches the handle (trim re-application)
        assert sup._robustify_target(for_restore=True) is sim.strategy

    def test_unreadable_ledger_degrades_to_fresh_ladder(self, tmp_path):
        path = tmp_path / "recovery_ledger.json"
        path.write_text("{torn")
        sup = RecoverySupervisor(_FakeSim([]), RecoveryPolicy(),
                                 ledger_path=str(path))
        assert sup._total_attempts == 0 and not sup._quarantine


# ---------------------------------------------------------------------------
class TestMitigationsOnRealSimulations:
    def test_in_graph_seeding_on_quarantining_strategy(self):
        sim = make_sim(strategy=QuarantiningStrategy(
            FedAvg(), QuarantinePolicy()
        ))
        sup = RecoverySupervisor(sim, RecoveryPolicy(quarantine_rounds=4))
        sup._engaged = True
        sup._pending_seed = [1, 3]
        sup.on_resume(2)
        q = np.asarray(sim.strategy.quarantine_mask(sim.server_state))
        np.testing.assert_array_equal(q, [0, 1, 0, 1, 0, 0])
        release = np.asarray(sim.server_state.quarantine.release_in)
        assert release[1] == 4.0 and release[3] == 4.0

    def test_robustify_swap_keeps_state_and_still_fits(self):
        sim = make_sim()
        sup = RecoverySupervisor(sim, RecoveryPolicy())
        before = jax.tree_util.tree_structure(sim.server_state)
        facts = sup._apply_robustify()
        assert facts == {"robustify": "swap", "method": "trimmed_mean",
                         "trim_fraction": 0.2}
        assert isinstance(sim.strategy, RobustFedAvg)
        # RobustFedAvg's state IS FedAvgState: restored checkpoints fit
        assert jax.tree_util.tree_structure(sim.server_state) == before
        hist = sim.fit(2)  # the rebuilt programs dispatch fine
        assert len(hist) == 2

    def test_robustify_tightens_an_existing_robust_strategy(self):
        sim = make_sim(strategy=RobustFedAvg(method="trimmed_mean",
                                             trim_fraction=0.2))
        sup = RecoverySupervisor(sim, RecoveryPolicy())
        facts = sup._apply_robustify()
        assert facts["robustify"] == "tighten"
        assert sim.strategy.trim_fraction == pytest.approx(0.3)

    def test_degrade_recomputes_fixed_fraction_k(self):
        """FixedFractionManager caches its realized count k at
        construction — the degrade rung must re-derive it or shrinking
        the fraction would be a silent no-op."""
        from fl4health_tpu.server.client_manager import FixedFractionManager

        mgr = FixedFractionManager(8, 0.5)
        assert mgr.k == 4
        sim = _FakeSim([], manager=mgr)
        sup = RecoverySupervisor(sim, RecoveryPolicy(cohort_shrink=0.5))
        facts = sup._apply_degrade()
        assert facts["cohort_fraction"]["to"] == pytest.approx(0.25)
        assert mgr.k == 2

    def test_robustify_not_applicable_to_stateful_strategies(self):
        from fl4health_tpu.strategies.fedopt import fed_adam

        sim = make_sim(strategy=fed_adam(lr=0.01))
        sup = RecoverySupervisor(sim, RecoveryPolicy())
        assert sup._robustify_target() is None


# ---------------------------------------------------------------------------
class TestSuspectScoring:
    def test_chaos_disclosure_and_nonfinite_dominate(self):
        ring = [
            {"round": 2, "mask": np.ones(4),
             "telemetry": {"nonfinite_loss": np.array([0, 0, 2, 0.0])},
             "fault": {"corrupted": [1], "kinds": {"scale": [1]}}},
        ]
        ranked = rank_suspects(ring)
        by_id = {s["client"]: s for s in ranked}
        assert set(by_id) == {1, 2}
        assert by_id[2]["score"] == pytest.approx(10.0)  # non-finite
        assert by_id[1]["score"] == pytest.approx(6.0)   # chaos disclosure
        assert any("chaos layer" in e for e in by_id[1]["evidence"])

    def test_verdict_clients_lead_then_ring_fills(self):
        sim = _FakeSim([])
        sup = RecoverySupervisor(
            sim, RecoveryPolicy(max_suspects=2, suspect_score_threshold=2.0)
        )
        sim.observability.flight_recorder.record_round(
            2, {}, mask=np.ones(4),
            telemetry={"nonfinite_loss": np.array([0, 0, 3, 0.0])},
        )
        suspects, ranked = sup._suspects({"clients": [0]})
        assert suspects == [0, 2]
        assert ranked[0]["client"] == 2


# ---------------------------------------------------------------------------
@pytest.mark.selfheal
class TestArmedNeverEngagedBitIdentity:
    @pytest.mark.parametrize("mode", ["pipelined", "chunked"])
    def test_armed_idle_policy_is_bit_identical(self, mode):
        base = make_sim(mode)
        hb = base.fit(3)
        armed = make_sim(mode, recovery=RecoveryPolicy())
        ha = armed.fit(3)
        assert _params_bytes(base) == _params_bytes(armed)
        assert [r.fit_losses for r in hb] == [r.fit_losses for r in ha]
        sup = armed._recovery_supervisor
        assert sup is not None and sup._total_attempts == 0


# ---------------------------------------------------------------------------
@pytest.mark.selfheal
class TestSelfHealDrill:
    """THE acceptance pin, both execution modes: probability-1 scale
    fault -> unsupervised FedAvg diverges and the watchdog halts it;
    the supervised run self-heals (rollback + quarantine of exactly the
    flight-recorder-named suspects) and converges within pinned tolerance
    of the fault-free trajectory — one self-consistent postmortem bundle
    per recovery attempt, ``/healthz`` back to 200 after probation."""

    N_ROUNDS = 10

    @pytest.fixture(scope="class")
    def fault_free_final(self):
        hist = make_sim("chunked", obs=make_obs()).fit(self.N_ROUNDS)
        return (hist[-1].fit_losses["backward"],
                hist[-1].eval_losses["checkpoint"])

    @pytest.mark.parametrize("mode", ["pipelined", "chunked"])
    def test_supervised_run_self_heals(self, mode, tmp_path,
                                       fault_free_final):
        # -- unsupervised arm: diverges, watchdog halts ------------------
        with pytest.raises(TrainingHealthError) as ei:
            make_sim(mode, obs=make_obs(), fault=SCALE_FAULT).fit(
                self.N_ROUNDS
            )
        assert ei.value.check == "loss_divergence"

        # -- supervised arm: rollback + quarantine + resume --------------
        obs = make_obs(output_dir=tmp_path / "obs")
        sim = make_sim(
            mode, obs=obs, fault=SCALE_FAULT, ckpt_dir=tmp_path / "ck",
            recovery=RecoveryPolicy(probation_rounds=3,
                                    quarantine_rounds=0),
        )
        hist = sim.fit(self.N_ROUNDS)
        assert [r.round for r in hist] == list(range(1, self.N_ROUNDS + 1))
        sup = sim._recovery_supervisor
        assert sup._attempts == {}  # probation passed: ladder reset
        assert not sup._engaged
        assert obs.unhealthy_reason is None  # /healthz back to 200
        # one self-consistent postmortem bundle per recovery attempt
        bundles = list_bundles(str(tmp_path / "obs"))
        assert len(bundles) == 2
        for b in bundles:
            verdict = load_bundle(b)["verdict"]
            assert verdict["kind"] == "training_health"
        # the recovery JSONL trail: one engage per attempt. Each attempt's
        # shutdown exports-and-clears the event log, so the full trail
        # lives in the per-attempt bundles' events.tail.jsonl plus the
        # final run's metrics.jsonl — exactly the operator's artifacts.
        events = []
        for b in bundles:
            events.extend(load_bundle(b)["events"])
        with open(tmp_path / "obs" / "metrics.jsonl") as f:
            events.extend(json.loads(line) for line in f if line.strip())
        events = [e for e in events if e.get("event") == "recovery"]
        engages = [e for e in events if e.get("phase") == "engage"]
        assert [e["rung"] for e in engages] == ["retry", "quarantine"]
        # exactly the flight-recorder-named suspects are quarantined: the
        # poisoned pair first, on the chaos layer's disclosure. The scale
        # fault is applied to the packet, so no client's own telemetry
        # shows it, and the ranking may also name an honest client whose
        # gradient norm is the cohort's lone 2-sigma outlier (with six
        # clients one of them can reach sqrt(5)); which one, if any, moves
        # with the numerics, so it is held to the policy's cap and to a
        # score under half the pair's.
        assert sorted(sup._quarantine) == sorted(engages[-1]["suspects"])
        for e in engages:
            assert sorted(e["suspects"][:2]) == sorted(POISONED)
            assert len(e["suspects"]) <= sup.policy.max_suspects
            scores = {s["client"]: s["score"] for s in e["suspect_scores"]}
            assert all(scores[c] < 0.5 * min(scores[p] for p in POISONED)
                       for c in e["suspects"][2:])
        assert any(e.get("phase") == "probation_passed" for e in events)
        # fl_recovery_* metrics landed
        snap = obs.registry.snapshot()
        assert snap["fl_recovery_attempts_total"]['{rung="retry"}'] == 1.0
        assert (snap["fl_recovery_attempts_total"]['{rung="quarantine"}']
                == 1.0)
        # -- convergence within pinned tolerance of fault-free -----------
        fit_ref, eval_ref = fault_free_final
        fit_final = hist[-1].fit_losses["backward"]
        eval_final = hist[-1].eval_losses["checkpoint"]
        assert abs(fit_final - fit_ref) < 0.2, (fit_final, fit_ref)
        assert abs(eval_final - eval_ref) < 0.6, (eval_final, eval_ref)

    def test_client_failures_taxonomy_heals_too(self):
        """accept_failures=False + a NaN-poisoned client: the structured
        ClientFailuresError names the client; the supervisor quarantines
        it (restart rollback — no checkpointer) and the run completes."""
        sim = make_sim(
            "pipelined", datasets=_datasets(4, poison_nan=(2,)),
            failure_policy=FailurePolicy(accept_failures=False),
            recovery=RecoveryPolicy(rungs=("quarantine",),
                                    quarantine_rounds=0),
        )
        hist = sim.fit(3)
        assert len(hist) == 3
        assert sim._recovery_supervisor.quarantined_ids(1) == [2]

    def test_unsupervised_client_failures_still_raise(self):
        sim = make_sim(
            "pipelined", datasets=_datasets(4, poison_nan=(2,)),
            failure_policy=FailurePolicy(accept_failures=False),
        )
        with pytest.raises(ClientFailuresError):
            sim.fit(3)
