"""FederatedSimulation(precision=...) wiring: precision-off is bit-identical
on BOTH execution modes, bf16 agrees across modes bitwise and lands within
the pinned tolerance of f32 on the CIFAR claim config, DP keeps its f32
clip->noise mechanism, and the policy composes with compression / mesh /
telemetry / early stopping."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.precision import PrecisionConfig

from tests.precision.conftest import make_cifar_sim, make_sim

BF16 = PrecisionConfig("bfloat16")
# bf16's ~8-bit mantissa against the claim config's loss magnitudes: the
# pinned tolerance for the bf16-vs-f32 trajectory gap (absolute, on the
# final round's training loss).
CIFAR_BF16_LOSS_ATOL = 0.05


class TestOffBitIdentity:
    def test_precision_none_is_bit_identical_on_both_modes(self):
        """THE off-pin: precision=None (and the explicit f32 no-op config)
        == pre-precision trajectories, pipelined AND chunked."""
        for mode in ("pipelined", "chunked"):
            base = [r.fit_losses["backward"]
                    for r in make_sim(execution_mode=mode).fit(3)]
            off = [r.fit_losses["backward"]
                   for r in make_sim(execution_mode=mode,
                                     precision=None).fit(3)]
            f32 = [r.fit_losses["backward"]
                   for r in make_sim(execution_mode=mode,
                                     precision=PrecisionConfig("f32")).fit(3)]
            assert base == off == f32, mode

    def test_duck_typed_config_rejected(self):
        with pytest.raises(TypeError, match="PrecisionConfig"):
            make_sim(precision={"compute_dtype": "bfloat16"})


class TestModeParity:
    def test_bf16_chunked_matches_pipelined_bitwise(self):
        losses = {}
        for mode in ("pipelined", "chunked"):
            hist = make_sim(execution_mode=mode, precision=BF16).fit(4)
            losses[mode] = [r.fit_losses["backward"] for r in hist]
        assert losses["pipelined"] == losses["chunked"]

    def test_fp16_chunked_matches_pipelined_bitwise(self):
        """The scaler state (scale/growth/skip) lives in the carried
        TrainState, so the two modes must evolve it — and the weights —
        identically."""
        cfg = PrecisionConfig("fp16")
        losses, skips = {}, {}
        for mode in ("pipelined", "chunked"):
            sim = make_sim(execution_mode=mode, precision=cfg)
            losses[mode] = [r.fit_losses["backward"] for r in sim.fit(4)]
            skips[mode] = np.asarray(sim.client_states.loss_scale["skipped"])
        assert losses["pipelined"] == losses["chunked"]
        np.testing.assert_array_equal(skips["pipelined"], skips["chunked"])

    def test_bf16_actually_changes_the_trajectory(self):
        base = [r.fit_losses["backward"] for r in make_sim().fit(3)]
        bf = [r.fit_losses["backward"]
              for r in make_sim(precision=BF16).fit(3)]
        assert base != bf


class TestCifarClaim:
    def test_bf16_within_pinned_tolerance_of_f32(self):
        """The acceptance pin: bf16 on the 4-client CIFAR claim config
        lands within CIFAR_BF16_LOSS_ATOL of the f32 trajectory."""
        base = [r.fit_losses["backward"] for r in make_cifar_sim().fit(4)]
        bf = [r.fit_losses["backward"]
              for r in make_cifar_sim(precision=BF16).fit(4)]
        assert all(np.isfinite(bf))
        assert abs(bf[-1] - base[-1]) < CIFAR_BF16_LOSS_ATOL
        # both arms actually learn (loss moves down) — the tolerance is not
        # satisfied vacuously by two flat lines
        assert bf[-1] < bf[0]


class TestDpComposition:
    def _dp_sim(self, precision=None, **kw):
        from fl4health_tpu.clients import engine
        from fl4health_tpu.clients.instance_level_dp import (
            InstanceLevelDpClientLogic,
        )

        from tests.precision.conftest import TinyNet

        logic = InstanceLevelDpClientLogic(
            engine.from_flax(TinyNet()), engine.masked_cross_entropy,
            clipping_bound=1.0, noise_multiplier=0.5,
        )
        return make_sim(logic=logic, precision=precision, **kw)

    def test_dp_under_bf16_keeps_f32_clip_noise(self):
        """Sigma/clip invariance: per-example grads arrive f32 at the
        master boundary (the clip bound and noise std are applied in f32,
        sigma unchanged — post-processing argument), and the clip-fraction
        telemetry stays a valid fraction close to the f32 run's."""
        from fl4health_tpu.observability import (
            MetricsRegistry,
            Observability,
            Tracer,
        )

        def clip_fracs(precision):
            obs = Observability(enabled=True, tracer=Tracer(),
                                registry=MetricsRegistry(),
                                sync_device=False)
            sim = self._dp_sim(precision=precision, observability=obs,
                               execution_mode="chunked")
            sim.fit(2)
            try:
                events = [e for e in obs.registry.events
                          if e.get("event") == "telemetry"]
                return np.asarray(events[-1]["clip_fraction"])
            finally:
                obs.shutdown()

        f32 = clip_fracs(None)
        bf = clip_fracs(BF16)
        assert ((bf >= 0) & (bf <= 1)).all()
        np.testing.assert_allclose(bf, f32, atol=0.26)

    def test_dp_bf16_trajectory_close_to_f32(self):
        base = [r.fit_losses["backward"] for r in self._dp_sim().fit(3)]
        bf = [r.fit_losses["backward"]
              for r in self._dp_sim(precision=BF16).fit(3)]
        # identical seeds -> identical noise draws (f32, independent of the
        # forward dtype); the residual gap is the bf16 forward only
        assert abs(bf[-1] - base[-1]) < 0.05

    def test_dp_grads_are_f32_under_bf16(self):
        import optax

        from fl4health_tpu.clients import engine
        from fl4health_tpu.clients.instance_level_dp import (
            InstanceLevelDpClientLogic,
        )
        from fl4health_tpu.precision import policy as px

        from tests.precision.conftest import TinyNet

        logic = InstanceLevelDpClientLogic(
            engine.from_flax(TinyNet()), engine.masked_cross_entropy,
            clipping_bound=1.0, noise_multiplier=0.5,
        )
        wrapped = px.wrap_logic_compute(logic, jnp.bfloat16)
        st = engine.create_train_state(
            wrapped, optax.sgd(0.1), jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.float32),
        )
        b = engine.Batch(x=jnp.ones((8, 4)), y=jnp.zeros((8,), jnp.int32),
                         example_mask=jnp.ones((8,)), step_mask=jnp.ones(()))
        _, grads = wrapped.value_and_grads(st, None, b, jax.random.PRNGKey(1))
        assert {str(l.dtype)
                for l in jax.tree_util.tree_leaves(grads)} == {"float32"}


class TestComposition:
    def test_compression_plus_precision_smoke(self):
        """CompressingStrategy sees f32 deltas (the packets are pushed f32
        master params): the composed run trains and both modes agree."""
        from fl4health_tpu.compression import CompressionConfig

        cfg = CompressionConfig(topk_fraction=0.5, quant_bits=8)
        losses = {}
        for mode in ("pipelined", "chunked"):
            sim = make_sim(execution_mode=mode, precision=BF16,
                           compression=cfg)
            losses[mode] = [r.fit_losses["backward"] for r in sim.fit(3)]
            # EF residual dtype unchanged: f32, like the master deltas
            res = sim.server_state.residual
            assert {str(l.dtype)
                    for l in jax.tree_util.tree_leaves(res)} == {"float32"}
        assert losses["pipelined"] == losses["chunked"]
        assert all(np.isfinite(losses["chunked"]))

    def test_robust_aggregation_plus_precision_smoke(self):
        from fl4health_tpu.resilience import RobustFedAvg

        hist = make_sim(strategy=RobustFedAvg("trimmed_mean"),
                        precision=BF16).fit(3)
        losses = [r.fit_losses["backward"] for r in hist]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_early_stopping_path_under_bf16(self):
        from fl4health_tpu.clients import engine as eng

        hist = make_sim(
            precision=BF16,
            early_stopping=eng.EarlyStoppingConfig(interval_steps=2,
                                                   patience=2),
        ).fit(2)
        assert all(np.isfinite([r.fit_losses["backward"] for r in hist]))

    def test_master_state_stays_f32(self):
        sim = make_sim(precision=BF16)
        sim.fit(2)
        for tree in (sim.client_states.params, sim.client_states.opt_state,
                     sim.global_params):
            dts = {str(l.dtype) for l in jax.tree_util.tree_leaves(tree)
                   if jnp.issubdtype(l.dtype, jnp.floating)}
            # <= : plain SGD's opt_state has no floating leaves at all
            assert dts <= {"float32"}
        assert {str(l.dtype)
                for l in jax.tree_util.tree_leaves(sim.global_params)} == \
            {"float32"}


@pytest.mark.multichip
class TestMeshComposition:
    def test_mesh_plus_precision_smoke(self):
        """The f32 master state shards over the clients axis exactly as
        without precision (the policy casts at apply time, never in the
        carried state), and the sharded bf16 run stays finite and close to
        the unsharded one."""
        from jax.sharding import PartitionSpec as P

        from fl4health_tpu.parallel.program import MeshConfig

        if len(jax.devices()) < 8:
            pytest.skip("needs the forced 8-host-device CPU platform")
        base = [r.fit_losses["backward"]
                for r in make_sim(n_clients=8, precision=BF16,
                                  execution_mode="chunked").fit(3)]
        sim = make_sim(n_clients=8, precision=BF16,
                       execution_mode="chunked",
                       mesh=MeshConfig(clients=8))
        hist = sim.fit(3)
        losses = [r.fit_losses["backward"] for r in hist]
        leaf = jax.tree_util.tree_leaves(sim.client_states.params)[0]
        assert leaf.sharding.spec == P("clients")
        assert leaf.dtype == jnp.float32  # the sharded master stays f32
        np.testing.assert_allclose(losses, base, atol=1e-4)


class TestTelemetryUnderPrecision:
    def test_norms_f32_finite_when_activations_large_in_bf16(self):
        """Telemetry grad/update norms are computed on the f32 boundary
        values: with large-magnitude data driving big bf16 activations,
        the recorded norms stay f32-finite (a bf16 norm accumulation would
        square into overflow far earlier)."""
        from fl4health_tpu.observability import (
            MetricsRegistry,
            Observability,
            Tracer,
        )

        obs = Observability(enabled=True, tracer=Tracer(),
                            registry=MetricsRegistry(), sync_device=False)
        sim = make_sim(precision=BF16, data_scale=80.0, observability=obs,
                       execution_mode="chunked")
        sim.fit(2)
        try:
            events = [e for e in obs.registry.events
                      if e.get("event") == "telemetry"]
            assert events
            gn = np.asarray(events[-1]["grad_norm_max"], np.float64)
            un = np.asarray(events[-1]["update_norm"], np.float64)
            assert np.isfinite(gn).all() and (gn > 0).all()
            assert np.isfinite(un).all()
        finally:
            obs.shutdown()

    def test_round_events_carry_dtype_and_skips(self, tmp_path):
        from fl4health_tpu.observability import Observability
        from fl4health_tpu.observability.registry import MetricsRegistry

        # a private registry: the process-wide one still holds the round
        # events of whichever test ran a sim without an output_dir before
        obs = Observability(enabled=True, output_dir=str(tmp_path),
                            registry=MetricsRegistry())
        sim = make_sim(precision=PrecisionConfig("fp16"), observability=obs,
                       execution_mode="chunked")
        sim.fit(2)
        events = [json.loads(line)
                  for line in open(os.path.join(str(tmp_path),
                                                "metrics.jsonl"))]
        rounds = [e for e in events if e.get("event") == "round"]
        assert rounds and all(
            r["compute_dtype"] == "float16" for r in rounds
        )
        assert all("loss_scale_skips" in r for r in rounds)
        telem = [e for e in events if e.get("event") == "telemetry"]
        assert telem and "loss_scale_skips" in telem[-1]
        progs = [e for e in events if e.get("event") == "program"]
        assert progs and all(
            p["precision"]["compute_dtype"] == "float16" for p in progs
        )
        manifest = json.load(open(os.path.join(str(tmp_path),
                                               "manifest.json")))
        assert manifest["config"]["precision"]["compute_dtype"] == "float16"

    def test_skips_summary_counts_all_clients_not_participants(self):
        """The per-client skip counters are CUMULATIVE, so the round-event
        scalar must sum over ALL clients — a participant-filtered sum
        would drop a benched client's history (non-monotone 'totals')."""
        from fl4health_tpu.observability.telemetry import summarize_host

        telemetry = {k: np.zeros(4, np.float32) for k in (
            "train_loss", "train_loss_min", "train_loss_max",
            "grad_norm_mean", "grad_norm_max", "update_norm",
            "clip_fraction", "nonfinite_params", "nonfinite_loss",
            "divergence", "nonfinite_eval_loss",
        )}
        telemetry["loss_scale_skips"] = np.asarray([4.0, 0.0, 1.0, 0.0])
        out = summarize_host(telemetry, np.asarray([0.0, 1.0, 1.0, 1.0]))
        assert out["loss_scale_skips"] == 5.0  # client 0's history kept

    def test_f32_round_events_carry_no_precision_fields(self, tmp_path):
        """Legacy log shape: a precision-less run must not grow the new
        fields (perf_report byte-stability rides on this)."""
        from fl4health_tpu.observability import Observability

        obs = Observability(enabled=True, output_dir=str(tmp_path))
        sim = make_sim(observability=obs, execution_mode="chunked")
        sim.fit(2)
        events = [json.loads(line)
                  for line in open(os.path.join(str(tmp_path),
                                                "metrics.jsonl"))]
        for r in (e for e in events if e.get("event") == "round"):
            assert "compute_dtype" not in r
            assert "loss_scale_skips" not in r
        for t in (e for e in events if e.get("event") == "telemetry"):
            assert "loss_scale_skips" not in t
        for p in (e for e in events if e.get("event") == "program"):
            assert "precision" not in p
        manifest = json.load(open(os.path.join(str(tmp_path),
                                               "manifest.json")))
        assert manifest["config"]["precision"] is None
