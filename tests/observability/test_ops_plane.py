"""Operations plane (observability/slo.py + adminplane.py): SLO engine,
degraded health, and the live admin retune endpoint.

The pinned contracts (ISSUE 19 acceptance):
- ops plane OFF (the default) leaves params and trajectories BIT-identical
  on pipelined, chunked, and cohort execution — and ARMING it does too
  (the plane only reads host floats the epilogue already held);
- a live ``POST /admin/scalars`` rebinding ``server_lr`` mid-``fit()``
  applies at the next round boundary with ZERO recompiles
  (CompileMonitor-pinned) and the retuned run is bit-reproducible from
  scratch via ``AdminPlane.schedule()`` + the journaled manifest;
- the endpoint refuses structurally: 401 unauthorized, 400 unknown
  scalar / bad body, 409 no-run / mid-chunk — never a silent no-op;
- ``/healthz`` answers all three states: 200 ok, 200 ``degraded: <slo>``,
  503 unhealthy (dead beats limping).
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import optax

from fl4health_tpu.clients import engine
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.observability import (
    AdminPlane,
    AdminRejection,
    MetricsRegistry,
    Observability,
    SLOPolicy,
    Tracer,
)
from fl4health_tpu.server.client_manager import FixedFractionManager
from fl4health_tpu.server.registry import CohortConfig
from fl4health_tpu.server.simulation import (
    EXEC_CHUNKED,
    EXEC_PIPELINED,
    ClientDataset,
    FederatedSimulation,
)
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.strategies.fedopt import fed_adam

pytestmark = pytest.mark.ops

N_CLASSES = 2


def make_datasets(n=2, rows=48, seed0=0):
    out = []
    for i in range(n):
        x, y = synthetic_classification(
            jax.random.PRNGKey(seed0 + i), rows, (4,), N_CLASSES
        )
        out.append(ClientDataset(
            np.asarray(x[:32]), np.asarray(y[:32]),
            np.asarray(x[32:]), np.asarray(y[32:]),
        ))
    return out


def make_sim(mode="pipelined", observability=None, strategy=None, n=2,
             cohort=None, manager=None, provider=None, seed=0):
    return FederatedSimulation(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(8,), n_outputs=N_CLASSES)),
            engine.masked_cross_entropy,
        ),
        tx=optax.sgd(0.05),
        strategy=strategy if strategy is not None else FedAvg(),
        datasets=make_datasets(n),
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2,
        seed=seed,
        execution_mode=mode,
        observability=observability,
        cohort=cohort,
        client_manager=manager,
        train_data_provider=provider,
    )


def make_obs(slo=None, admin_token=None, http_port=None):
    return Observability(
        enabled=True, tracer=Tracer(), registry=MetricsRegistry(),
        sync_device=False, flight_recorder=False,
        slo=slo, admin_token=admin_token, http_port=http_port,
    )


def armed_policy():
    # generous thresholds: arming the full engine must not change the run
    return SLOPolicy(min_rounds_per_hour=0.001, max_eval_loss=1e9,
                     stall_rounds=10_000, max_bytes_per_client=1e15,
                     max_mttr_s=1e9, max_straggler_p99=1e9)


def _params_bytes(sim):
    from flax import serialization

    return serialization.to_bytes(jax.device_get(sim.global_params))


def _post(url, body, token=None):
    """POST helper returning (status, parsed JSON body) without raising."""
    headers = {"Content-Type": "application/json"}
    if token is not None:
        headers[AdminPlane.AUTH_HEADER] = token
    data = body if isinstance(body, bytes) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, headers=headers,
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as err:
        raw = err.read().decode()
        try:
            return err.code, json.loads(raw)
        except ValueError:
            return err.code, raw


class TestOffPathUntouched:
    def test_unarmed_observability_builds_no_ops_plane(self):
        obs = make_obs()
        assert obs.slo is None and obs.admin is None
        assert obs.timeseries is None
        assert obs.observe_round_kpis(1, {"fit_s": 1.0}) is None
        obs.shutdown()

    def test_admin_plane_refuses_empty_token(self):
        with pytest.raises(ValueError, match="shared secret"):
            AdminPlane("")


class TestBitIdentity:
    @pytest.mark.parametrize("mode", ["pipelined", "chunked"])
    def test_armed_vs_off_bit_identical(self, mode):
        """THE acceptance pin: SLO engine + admin plane armed never touch
        the trajectory on either execution mode (forced chunked keeps the
        admin plane inert — submits are refused, arming costs nothing)."""
        runs = {}
        for armed in (True, False):
            obs = (make_obs(slo=armed_policy(), admin_token="t")
                   if armed else make_obs())
            sim = make_sim(mode=mode, observability=obs)
            hist = sim.fit(3)
            runs[armed] = (
                _params_bytes(sim),
                [(r.fit_losses, r.eval_losses) for r in hist],
            )
            obs.shutdown()
        assert runs[True][0] == runs[False][0]
        assert runs[True][1] == runs[False][1]

    def test_armed_vs_off_bit_identical_cohort(self):
        """Same pin under cohort-slot execution (SLO arm only: an armed
        admin plane demotes the auto mode choice to pipelined, which is
        its own pinned behavior below)."""
        runs = {}
        for armed in (True, False):
            obs = make_obs(slo=armed_policy() if armed else None)
            sim = make_sim(
                mode="auto", observability=obs, n=6,
                cohort=CohortConfig(slots=3),
                manager=FixedFractionManager(6, 0.5),
            )
            hist = sim.fit(3)
            runs[armed] = (
                _params_bytes(sim),
                [(r.fit_losses, r.eval_losses) for r in hist],
            )
            obs.shutdown()
        assert runs[True][0] == runs[False][0]
        assert runs[True][1] == runs[False][1]

    def test_admin_armed_demotes_auto_mode_to_pipelined(self):
        """Live retunes need per-round host boundaries: an armed admin
        plane steers the AUTO choice to pipelined (forced chunked stays
        legal — submits are then refused as mid_chunk)."""
        obs = make_obs(admin_token="t")
        sim = make_sim(mode="auto", observability=obs)
        mode, reason = sim._select_execution_mode(3)
        assert mode == EXEC_PIPELINED
        assert "admin" in reason
        obs.shutdown()
        # without the admin plane the same sim is chunk-eligible
        obs2 = make_obs()
        sim2 = make_sim(mode="auto", observability=obs2)
        assert sim2._select_execution_mode(3)[0] == EXEC_CHUNKED
        obs2.shutdown()


class TestEndpointConformance:
    @pytest.fixture
    def served(self):
        obs = make_obs(slo=SLOPolicy(max_eval_loss=1.0), admin_token="s3cr3t",
                       http_port=0)
        yield obs
        obs.shutdown()

    def test_healthz_three_states(self, served):
        url = served.scrape_url + "/healthz"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200 and r.read() == b"ok\n"
        served.mark_degraded("eval_loss")
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200 and r.read() == b"degraded: eval_loss\n"
        # dead beats limping
        served.mark_unhealthy("watchdog: loss diverged")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url, timeout=5)
        assert err.value.code == 503
        assert b"watchdog" in err.value.read()
        served.mark_healthy()
        served.clear_degraded()
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.read() == b"ok\n"

    def test_head_answers_every_get_route(self, served):
        for path in ("/metrics", "/healthz", "/manifest", "/admin/slo"):
            with urllib.request.urlopen(served.scrape_url + path,
                                        timeout=5) as r:
                got = len(r.read())
            req = urllib.request.Request(served.scrape_url + path,
                                         method="HEAD")
            with urllib.request.urlopen(req, timeout=5) as r:
                assert r.status == 200
                assert r.read() == b""  # headers only
                # Content-Length advertises the GET body it elides
                assert int(r.headers["Content-Length"]) == got

    def test_wrong_method_is_405_with_allow_not_501(self, served):
        # POST on a read route
        status, _ = _post(served.scrape_url + "/metrics", {})
        assert status == 405
        # GET on the admin mutation route
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(served.scrape_url + "/admin/scalars",
                                   timeout=5)
        assert err.value.code == 405
        assert err.value.headers["Allow"] == "POST"
        # an unsupported verb anywhere known
        req = urllib.request.Request(served.scrape_url + "/metrics",
                                     method="DELETE")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5)
        assert err.value.code == 405
        assert err.value.headers["Allow"] == "GET, HEAD"
        # unknown paths stay 404 for every verb
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(served.scrape_url + "/nope", timeout=5)
        assert err.value.code == 404

    def test_admin_slo_serves_standing(self, served):
        with urllib.request.urlopen(served.scrape_url + "/admin/slo",
                                    timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["objectives_armed"] == ["eval_loss"]
        assert doc["state"] == "ok"
        assert doc["policy"]["max_eval_loss"] == 1.0

    def test_admin_routes_absent_when_unarmed(self):
        obs = make_obs(http_port=0)  # no slo, no admin token
        try:
            for path in ("/admin/slo", "/admin/scalars"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(obs.scrape_url + path, timeout=5)
                assert err.value.code == 404
        finally:
            obs.shutdown()

    def test_submit_rejections_are_structured(self, served):
        url = served.scrape_url + "/admin/scalars"
        # 401: missing, then wrong token
        status, doc = _post(url, {"server_lr": 0.1})
        assert (status, doc["error"]) == (401, "unauthorized")
        status, doc = _post(url, {"server_lr": 0.1}, token="wrong")
        assert (status, doc["error"]) == (401, "unauthorized")
        # 400: body not JSON
        status, doc = _post(url, b"not json{", token="s3cr3t")
        assert (status, doc["error"]) == (400, "bad_request")
        # 409: authorized but no fit() bound yet
        status, doc = _post(url, {"server_lr": 0.1}, token="s3cr3t")
        assert (status, doc["error"]) == (409, "no_active_run")
        # bind a pipelined run: unknown scalars now answer 400 and NAME
        # the registered set
        served.admin.bind_run(fed_adam(0.1), EXEC_PIPELINED)
        status, doc = _post(url, {"nope": 1.0}, token="s3cr3t")
        assert (status, doc["error"]) == (400, "unknown_scalar")
        assert "server_lr" in doc["detail"]
        status, doc = _post(url, {"server_lr": "abc"}, token="s3cr3t")
        assert (status, doc["error"]) == (400, "bad_request")
        # server_lr has no owner on a plain-FedAvg chain
        served.admin.bind_run(FedAvg(), EXEC_PIPELINED)
        status, doc = _post(url, {"server_lr": 0.1}, token="s3cr3t")
        assert (status, doc["error"]) == (409, "inapplicable_scalar")
        # chunked runs have no host boundary to apply at
        served.admin.bind_run(fed_adam(0.1), EXEC_CHUNKED)
        status, doc = _post(url, {"server_lr": 0.1}, token="s3cr3t")
        assert (status, doc["error"]) == (409, "mid_chunk")

    def test_static_scalar_refused_not_silently_ignored(self, served):
        from fl4health_tpu.resilience import RobustFedAvg

        served.admin.bind_run(RobustFedAvg(trim_fraction=0.1),
                              EXEC_PIPELINED)
        with pytest.raises(AdminRejection) as err:
            served.admin.submit({"trim_fraction": 0.2})
        assert err.value.status == 409
        assert err.value.error == "static_scalar"
        assert "sweep" in err.value.detail

    def test_all_or_nothing_validation(self, served):
        """One bad scalar rejects the WHOLE submit — no partial retunes."""
        served.admin.bind_run(fed_adam(0.1), EXEC_PIPELINED)
        with pytest.raises(AdminRejection):
            served.admin.submit({"server_lr": 0.2, "nope": 1.0})
        assert served.admin.drain(1) == {}


class TestLiveRetuneDrill:
    def test_live_retune_zero_recompiles_and_bit_reproducible(self):
        """THE acceptance drill: a mid-fit POST rebinding server_lr lands
        at the next round boundary with zero recompiles, is journaled to
        the manifest, and replaying the journal via ``schedule()`` on a
        fresh run reproduces the live-retuned trajectory bit-exactly."""
        token = "drill-token"
        posted = {}

        def posting_provider(rnd):
            if rnd == 3 and "resp" not in posted:
                posted["resp"] = _post(
                    obs_live.scrape_url + "/admin/scalars",
                    {"server_lr": 0.02}, token=token,
                )
            return None

        noop_provider = lambda rnd: None  # noqa: E731

        # --- live run: POST fired synchronously from the round-3 provider
        obs_live = make_obs(admin_token=token, http_port=0)
        sim_live = make_sim(strategy=fed_adam(0.1), observability=obs_live,
                            provider=posting_provider)
        hist_live = sim_live.fit(6)
        status, doc = posted["resp"]
        assert status == 200
        assert doc["accepted"] == {"server_lr": 0.02}
        assert doc["applies"] == "next_round_boundary"

        # zero recompiles: every round after the first, INCLUDING the
        # retuned one, reuses the warm executables. How many compiles round
        # 1 itself counts is this process's history, not the claim: the
        # introspector builds the round programs in the prologue, and the
        # round's own dispatch compiles again only where JAX has not
        # already built the same program in this process. That the counter
        # behind ``compiles`` is live shows in the run's total.
        rounds = [e for e in obs_live.registry.events
                  if e["event"] == "round"]
        assert len(rounds) == 6
        assert [r["compiles"] for r in rounds[1:]] == [0] * 5
        assert obs_live.registry.counter(
            "jax_backend_compiles_total").value > 0

        # journaled three ways: admin JSONL event, journal, manifest
        admin_events = [e for e in obs_live.registry.events
                        if e["event"] == "admin"]
        assert len(admin_events) == 1
        assert admin_events[0]["round"] == 3
        assert admin_events[0]["scalars"] == {"server_lr": 0.02}
        assert obs_live.admin.journal()[0]["round"] == 3
        assert obs_live.manifest["admin"] == {
            "enabled": True,
            "retunes": [{"round": 3, "scalars": {"server_lr": 0.02},
                         "source": "live"}],
        }
        live = (_params_bytes(sim_live),
                [(r.fit_losses, r.eval_losses) for r in hist_live])
        obs_live.shutdown()

        # --- replay: a fresh run fed the journal via schedule()
        obs_replay = make_obs(admin_token=token)
        obs_replay.admin.schedule(3, {"server_lr": 0.02})
        sim_replay = make_sim(strategy=fed_adam(0.1),
                              observability=obs_replay,
                              provider=noop_provider)
        hist_replay = sim_replay.fit(6)
        replay = (_params_bytes(sim_replay),
                  [(r.fit_losses, r.eval_losses) for r in hist_replay])
        obs_replay.shutdown()
        assert live == replay

        # --- control: the un-retuned run shares the prefix, then diverges
        obs_plain = make_obs()
        sim_plain = make_sim(strategy=fed_adam(0.1), observability=obs_plain,
                             provider=noop_provider)
        hist_plain = sim_plain.fit(6)
        plain_losses = [(r.fit_losses, r.eval_losses) for r in hist_plain]
        obs_plain.shutdown()
        assert plain_losses[:2] == live[1][:2]  # rounds 1-2 untouched
        assert plain_losses != live[1]  # the retune took effect
        assert _params_bytes(sim_plain) != live[0]
