"""Round-loop observability smoke tests (the ISSUE acceptance surface): a
2-round CPU run with observability enabled writes a Perfetto-loadable Chrome
trace with named spans per round plus non-zero compile/byte counters; with
observability disabled no artifacts and no extra device syncs appear."""

import json

import jax
import optax
import pytest

from fl4health_tpu.clients import engine
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.observability import (
    MetricsRegistry,
    Observability,
    Tracer,
)
from fl4health_tpu.reporting.base import JsonReporter
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg

N_ROUNDS = 2


def _sim(**kwargs):
    x, y = synthetic_classification(jax.random.PRNGKey(0), 48, (4,), 2)
    datasets = [
        ClientDataset(x[:16], y[:16], x[32:40], y[32:40]),
        ClientDataset(x[16:32], y[16:32], x[40:], y[40:]),
    ]
    defaults = dict(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(8,), n_outputs=2)),
            engine.masked_cross_entropy,
        ),
        tx=optax.sgd(0.05),
        strategy=FedAvg(),
        datasets=datasets,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2,
        seed=0,
    )
    defaults.update(kwargs)
    return FederatedSimulation(**defaults)


@pytest.fixture
def obs(tmp_path):
    # private tracer/registry: process-global state stays untouched.
    # per_round_spans opts into the per-round span timeline these tests
    # assert on (it forces the pipelined path; plain enabled observability
    # now keeps the chunked fast path — tests/observability/test_telemetry.py
    # covers that side).
    return Observability(
        enabled=True,
        output_dir=str(tmp_path / "obs"),
        tracer=Tracer(),
        registry=MetricsRegistry(),
        per_round_spans=True,
    )


class TestEnabled:
    def test_two_round_run_emits_spans_and_counters(self, obs, tmp_path):
        rep = JsonReporter(output_folder=str(tmp_path), run_id="obsrun")
        sim = _sim(observability=obs, reporters=[rep])
        history = sim.fit(N_ROUNDS)
        assert len(history) == N_ROUNDS

        # --- trace artifact: Perfetto-loadable, named spans per round -----
        trace_path = tmp_path / "obs" / "trace.json"
        with open(trace_path) as f:
            doc = json.load(f)
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        for name in ("configure_fit", "fit_round", "aggregate", "eval_round",
                     "checkpoint", "report"):
            per_round = [
                s for s in spans
                if s["name"] == name and s["args"].get("round") in (1, 2)
            ]
            rounds_covered = {s["args"]["round"] for s in per_round}
            assert rounds_covered == {1, 2}, (
                f"span {name!r} missing for some round: {rounds_covered}"
            )
        round_spans = [s for s in spans if s["name"] == "round"]
        assert len(round_spans) == N_ROUNDS
        # phase spans nest inside their round span
        fit1 = next(s for s in spans
                    if s["name"] == "fit_round" and s["args"]["round"] == 1)
        r1 = next(s for s in round_spans if s["args"]["round"] == 1)
        assert r1["ts"] <= fit1["ts"]
        assert fit1["ts"] + fit1["dur"] <= r1["ts"] + r1["dur"] + 1e-6
        # honest device time was measured on the enabled path
        assert fit1["args"]["device_wait_s"] >= 0.0

        # --- metrics snapshot: compile + byte counters non-zero -----------
        snap = obs.snapshot()
        assert snap["jax_backend_compiles_total"] > 0
        assert snap["fl_broadcast_bytes_total"] > 0
        assert snap["fl_gather_bytes_total"] > 0
        assert snap["fl_rounds_total"] == N_ROUNDS
        assert snap["fl_participating_clients"] == 2.0

        # --- JSONL event log: one 'round' record per round -----------------
        with open(tmp_path / "obs" / "metrics.jsonl") as f:
            events = [json.loads(line) for line in f]
        rounds = [e for e in events if e["event"] == "round"]
        assert [e["round"] for e in rounds] == [1, 2]
        for e in rounds:
            assert e["broadcast_bytes"] > 0
            assert e["fit_s"] > 0
        # round 1 pays the XLA compiles; round 2 must not recompile
        assert rounds[0]["compiles"] > 0
        assert rounds[1]["compiles"] == 0

        # --- Prometheus exposition written -------------------------------
        prom = (tmp_path / "obs" / "metrics.prom").read_text()
        assert "# TYPE fl_rounds_total counter" in prom
        assert "# TYPE jax_backend_compiles_total counter" in prom

        # --- reporter bridge: same data reaches ReportsManager sinks ------
        report = rep.data["rounds"]["1"]["observability"]
        assert report["compiles"] > 0
        assert report["broadcast_bytes"] > 0
        assert "observability_artifacts" in rep.data

    def test_fit_shutdown_detaches_and_rearms(self, tmp_path):
        """Review findings: fit() must disarm the hooks at the end — the
        compile monitor detaches (no double counting across runs), an
        owned tracer is released and cleared (no unbounded growth, no stale
        spans re-exported) — and a second fit() re-arms everything."""
        tr = Tracer(enabled=False)  # plays the process-global default
        reg = MetricsRegistry()
        obs = Observability(
            enabled=True, output_dir=str(tmp_path / "obs"),
            tracer=tr, registry=reg, per_round_spans=True,
        )
        sim = _sim(observability=obs)
        sim.fit(1)
        assert not obs.compile_monitor.installed
        assert tr.enabled is False and tr.events == []
        # run 2 re-arms and its JSONL log contains ONLY its own rounds
        sim.fit(1)
        with open(tmp_path / "obs" / "metrics.jsonl") as f:
            rounds = [json.loads(l) for l in f if '"round"' in l]
        assert len([r for r in rounds if r["event"] == "round"]) == 1
        # trace.json from run 2 holds exactly run 2's round span
        with open(tmp_path / "obs" / "trace.json") as f:
            doc = json.load(f)
        assert len([e for e in doc["traceEvents"]
                    if e.get("ph") == "X" and e["name"] == "round"]) == 1

    def test_shutdown_runs_even_when_a_round_raises(self, tmp_path, monkeypatch):
        """Review finding: a ClientFailuresError escaping the round loop must
        still disarm the hooks and export the failed run's artifacts."""
        tr = Tracer(enabled=False)
        obs = Observability(
            enabled=True, output_dir=str(tmp_path / "obs"),
            tracer=tr, registry=MetricsRegistry(), per_round_spans=True,
        )
        sim = _sim(observability=obs)

        def boom(rnd, vb, vc):
            raise RuntimeError("client failure mid-round")

        monkeypatch.setattr(sim, "_run_round", boom)
        with pytest.raises(RuntimeError, match="mid-round"):
            sim.fit(2)
        assert not obs.compile_monitor.installed
        assert tr.enabled is False
        assert (tmp_path / "obs" / "trace.json").exists()

    def test_no_output_dir_keeps_events_readable(self):
        """Review finding: with output_dir=None nothing is dumped, so
        shutdown must NOT clear the event log — programmatic access
        (registry.events) is the only surface left."""
        reg = MetricsRegistry()
        obs = Observability(enabled=True, tracer=Tracer(), registry=reg)
        sim = _sim(observability=obs)
        sim.fit(1)
        rounds = [e for e in reg.events if e["event"] == "round"]
        assert len(rounds) == 1

    def test_test_split_device_time_fenced(self, obs):
        """Review finding: the separate test-loader eval's device time must
        land in the eval span's device_wait_s, not leak into host time."""
        import numpy as np

        import jax as _jax
        from fl4health_tpu.datasets.synthetic import synthetic_classification

        x, y = synthetic_classification(_jax.random.PRNGKey(1), 60, (4,), 2)
        ds = [ClientDataset(x[:16], y[:16], x[32:40], y[32:40],
                            x[48:54], y[48:54]),
              ClientDataset(x[16:32], y[16:32], x[40:48], y[40:48],
                            x[54:60], y[54:60])]
        sim = _sim(observability=obs, datasets=ds)
        hist = sim.fit(1)
        assert any(k.startswith("test - ") for k in hist[0].eval_losses)
        span = obs.tracer.spans_named("eval_round")[0]
        assert span["args"]["device_wait_s"] >= 0.0

    def test_shutdown_leaves_caller_owned_tracer_alone(self):
        tr = Tracer(enabled=True)  # caller enabled it; we must not reset it
        obs = Observability(enabled=True, tracer=tr, registry=MetricsRegistry())
        with tr.span("caller_span"):
            pass
        obs.shutdown()
        assert tr.enabled is True
        assert len(tr.spans_named("caller_span")) == 1

    def test_profile_round_capture(self, tmp_path):
        obs = Observability(
            enabled=True, output_dir=str(tmp_path / "obs"),
            tracer=Tracer(), registry=MetricsRegistry(),
            profile_round_idx=2,
        )
        sim = _sim(observability=obs)
        sim.fit(N_ROUNDS)
        xprof = tmp_path / "obs" / "xprof"
        produced = [p for p in xprof.rglob("*") if p.is_file()]
        assert produced, "profile_round_idx produced no XProf artifacts"

    def test_failure_counters(self, obs):
        import numpy as np

        sim = _sim(observability=obs)
        sim.fit(1)
        # poison one client's training labels mid-run is heavyweight; instead
        # exercise the accounting path directly with a synthetic failure
        sim._record_round_metrics(
            99, sim.history[-1], np.asarray([1.0, 1.0]),
            {"backward": np.asarray([np.inf, 1.0])}, [0],
            0.0, 0.0, 0.0,
        )
        snap = obs.snapshot()
        assert snap["fl_client_failures_total"] == 1.0
        # dispersion gauges ignore the non-finite failed row
        assert snap["fl_fit_loss_std"] == 0.0


class TestProgramIntrospection:
    """ISSUE 4 tentpole: build-time compiled-program introspection feeds
    ProgramReports, measured per-round FLOPs and the round records — with
    zero per-round cost and no trajectory change."""

    def test_pipelined_fit_introspects_round_programs(self):
        # no output_dir: the JSONL events stay readable after shutdown
        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry(), per_round_spans=True)
        sim = _sim(observability=obs)
        sim.fit(1)
        reports = obs.introspector.reports
        # telemetry defaults on -> the _t variants are what fit() dispatches
        assert "fit_round_t" in reports and "eval_round_t" in reports
        fit_rep = reports["fit_round_t"]
        assert fit_rep.flops > 0 and fit_rep.bytes_accessed > 0
        assert fit_rep.peak_hbm_bytes > 0
        assert fit_rep.compile_seconds > 0
        # measured per-round numbers land in the round JSONL event
        rounds = [e for e in obs.registry.events if e["event"] == "round"]
        assert rounds[0]["program_flops_round"] == pytest.approx(
            fit_rep.flops + reports["eval_round_t"].flops
        )
        assert rounds[0]["tflops_measured"] > 0
        # CPU has no published peak: measured MFU must be absent, not fake
        assert "mfu_pct" not in rounds[0]
        # program events in the JSONL log (perf_report renders them)
        progs = [e for e in obs.registry.events if e["event"] == "program"]
        assert {p["name"] for p in progs} == {"fit_round_t", "eval_round_t"}

    def test_chunked_fit_introspects_scan_program(self):
        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry())
        sim = _sim(observability=obs)
        sim.fit(2)
        assert sim._active_execution_mode == "chunked_scan"
        rep = obs.introspector.reports["fit_chunk_eval"]
        assert rep.rounds_per_dispatch == 2
        assert rep.flops > 0
        # per-round flops = the scan program's flops amortized
        rounds = [e for e in obs.registry.events if e["event"] == "round"]
        assert rounds[0]["program_flops_round"] == pytest.approx(rep.flops / 2)

    def test_introspection_off_no_reports_same_trajectory(self):
        on = Observability(enabled=True, tracer=Tracer(),
                           registry=MetricsRegistry())
        off = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry(), introspection=False)
        h_on = _sim(observability=on).fit(N_ROUNDS)
        h_off = _sim(observability=off).fit(N_ROUNDS)
        assert off.introspector.reports == {}
        rounds_off = [e for e in off.registry.events if e["event"] == "round"]
        assert "program_flops_round" not in rounds_off[0]
        # bit-identical trajectories (acceptance criterion)
        assert [r.fit_losses for r in h_on] == [r.fit_losses for r in h_off]
        assert [r.eval_losses for r in h_on] == [r.eval_losses for r in h_off]

    def test_introspection_failure_does_not_break_fit(self, monkeypatch):
        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry())
        sim = _sim(observability=obs)

        def boom(*a, **k):
            raise RuntimeError("no cost model on this backend")

        monkeypatch.setattr(obs.introspector, "introspect_jit", boom)
        assert len(sim.fit(1)) == 1  # fit survives; MFU fields just absent

    def test_test_split_program_gets_own_report(self, obs):
        import jax as _jax
        from fl4health_tpu.datasets.synthetic import synthetic_classification

        x, y = synthetic_classification(_jax.random.PRNGKey(1), 60, (4,), 2)
        ds = [ClientDataset(x[:16], y[:16], x[32:40], y[32:40],
                            x[48:54], y[48:54]),
              ClientDataset(x[16:32], y[16:32], x[40:48], y[40:48],
                            x[54:60], y[54:60])]
        sim = _sim(observability=obs, datasets=ds)
        sim.fit(1)
        assert "eval_round_t_test" in obs.introspector.reports


def _test_split_datasets():
    x, y = synthetic_classification(jax.random.PRNGKey(1), 60, (4,), 2)
    return [ClientDataset(x[:16], y[:16], x[32:40], y[32:40],
                          x[48:54], y[48:54]),
            ClientDataset(x[16:32], y[16:32], x[40:48], y[40:48],
                          x[54:60], y[54:60])]


class TestIntrospectionOncePerProgram:
    """ISSUE 28: the round programs are built once, in the constructor, so
    a second fit() on one simulation records the first call's reports
    instead of lowering, loading and walking each program again."""

    @pytest.mark.parametrize("test_split", [False, True])
    def test_second_pipelined_fit_reuses_reports(self, test_split):
        def run(introspection):
            o = Observability(enabled=True, tracer=Tracer(),
                              registry=MetricsRegistry(),
                              per_round_spans=True,
                              introspection=introspection)
            kw = {"datasets": _test_split_datasets()} if test_split else {}
            sim = _sim(observability=o, **kw)
            # fit() continues from the state the last call left, numbers
            # its rounds from 1 again and appends to the one history
            sim.fit(2)
            first = dict(o.introspector.reports)
            return o, sim, first, list(sim.fit(2))

        obs, sim, first, history = run(True)
        n = 3 if test_split else 2
        assert [s["args"]["cached"]
                for s in obs.tracer.spans_named("introspect")] == [
            f"0/{n}", f"{n}/{n}"]
        assert len(first) == n and obs.introspector.reports == first
        snap = obs.registry.snapshot()["fl_program_introspections_total"]
        assert all(v == 1.0 for v in snap.values()) and len(snap) == 2 * n
        # every call's log says what ran: the program events twice over
        progs = [e["name"] for e in obs.registry.events
                 if e["event"] == "program"]
        assert len(progs) == 2 * n and set(progs) == set(first)
        # the second call's rounds still carry measured FLOPs
        assert sim._round_program_flops == pytest.approx(
            sum(r.flops for r in first.values()))
        rounds = [e for e in obs.registry.events if e["event"] == "round"]
        assert [e["round"] for e in rounds] == [1, 2, 1, 2]
        assert all(e["program_flops_round"] == rounds[0]["program_flops_round"]
                   for e in rounds)
        # ... and the trajectory is that of a run that never introspects
        history_off = run(False)[3]
        assert len(history) == 4
        assert ([r.fit_losses for r in history]
                == [r.fit_losses for r in history_off])
        assert ([r.eval_losses for r in history]
                == [r.eval_losses for r in history_off])
        # the second call trained on: it did not replay the first
        assert history[2].fit_losses != history[0].fit_losses

    def test_chunked_fit_keyed_by_chunk_length(self):
        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry())
        sim = _sim(observability=obs)
        for n in (2, 3, 3):
            sim.fit(n)
            assert sim._active_execution_mode == "chunked_scan"
            assert obs.introspector.reports[
                "fit_chunk_eval"].rounds_per_dispatch == n
        assert [s["args"]["cached"]
                for s in obs.tracer.spans_named("introspect")] == [
            "0/1", "0/1", "1/1"]
        assert obs.registry.snapshot()["fl_program_introspections_total"] == {
            '{program="fit_chunk_eval",result="miss"}': 2.0,
            '{program="fit_chunk_eval",result="hit"}': 1.0,
        }

    def test_rebuilt_simulation_on_one_handle_is_captured_afresh(self):
        """Another simulation's programs are other jitted objects: sharing
        the Observability handle must not hand it the first one's reports."""
        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry(), per_round_spans=True)
        _sim(observability=obs).fit(1)
        _sim(observability=obs, local_steps=3).fit(1)
        assert [s["args"]["cached"]
                for s in obs.tracer.spans_named("introspect")] == [
            "0/2", "0/2"]


class TestDisabled:
    def test_disabled_default_no_artifacts_no_spans(self, tmp_path):
        sim = _sim()
        assert sim.observability.enabled is False
        history = sim.fit(N_ROUNDS)
        assert len(history) == N_ROUNDS
        # nothing exported, no span events recorded into the default tracer
        assert sim.observability.export() == {}
        assert not (tmp_path / "obs").exists()

    def test_disabled_fence_adds_no_sync(self):
        """The disabled hot path must not introduce block_until_ready: the
        fence is a pure pass-through (identity, zero wait)."""
        sim = _sim()
        obj = object()
        out, wait = sim.observability.fence(obj)
        assert out is obj and wait == 0.0

    def test_disabled_span_is_shared_noop(self):
        from fl4health_tpu.observability.spans import _NULL_SPAN

        sim = _sim()
        assert sim.observability.span("round", round=1) is _NULL_SPAN

    def test_histories_match_enabled_vs_disabled(self, obs):
        """Instrumentation must not perturb the training trajectory."""
        h_dis = _sim().fit(N_ROUNDS)
        h_en = _sim(observability=obs).fit(N_ROUNDS)
        assert h_dis[-1].eval_losses["checkpoint"] == pytest.approx(
            h_en[-1].eval_losses["checkpoint"]
        )
        assert h_dis[-1].fit_losses["backward"] == pytest.approx(
            h_en[-1].fit_losses["backward"]
        )
