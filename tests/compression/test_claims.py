"""THE pinned claims of the compressed-exchange PR:

- accuracy-vs-bytes on the 4-client CIFAR config: int8 + top-k at k=10%
  trains within a small loss delta of dense FedAvg while the estimated
  wire bytes drop >=8x (the BENCH `compression` block pins the same point
  on real frames of the bench model);
- the resilience robustness claim survives compression: the amplified
  sign-flip FaultPlan from the resilience suite re-run under int8+top-k —
  plain FedAvg diverges, RobustFedAvg(trimmed_mean) keeps converging on
  the SAME lossy updates."""

import jax
import numpy as np
import pytest

from fl4health_tpu.compression import (
    CompressionConfig,
    estimate_wire_nbytes,
)
from fl4health_tpu.core.pytree import tree_nbytes
from fl4health_tpu.resilience import ClientFault, FaultPlan, RobustFedAvg
from fl4health_tpu.strategies.fedavg import FedAvg

from tests.compression.conftest import make_cifar_sim, make_sim

CLAIM_CFG = CompressionConfig(topk_fraction=0.1, quant_bits=8)


class TestAccuracyVsBytes:
    ROUNDS = 5

    def test_cifar_int8_topk10_within_loss_delta_of_dense(self):
        dense = [r.fit_losses["backward"]
                 for r in make_cifar_sim().fit(self.ROUNDS)]
        comp = [r.fit_losses["backward"]
                for r in make_cifar_sim(compression=CLAIM_CFG).fit(self.ROUNDS)]
        assert all(np.isfinite(comp)), comp
        assert comp[-1] < comp[0], comp  # still converging
        # pinned delta: final loss within 10% (relative) + small absolute
        # slack of the dense run's
        assert abs(comp[-1] - dense[-1]) <= 0.1 * abs(dense[-1]) + 0.05, (
            dense, comp,
        )

    def test_wire_bytes_reduction_at_least_8x(self):
        sim = make_cifar_sim(compression=CLAIM_CFG)
        gp = sim.strategy.global_params(sim.server_state)
        logical = tree_nbytes(gp)
        wire = estimate_wire_nbytes(gp, CLAIM_CFG)
        assert logical / wire >= 8.0, (logical, wire)

    def test_round_events_report_the_ratio(self):
        import json
        import os
        import tempfile

        from fl4health_tpu.observability import Observability
        from fl4health_tpu.observability.registry import MetricsRegistry

        d = tempfile.mkdtemp()
        # a private registry: the process-wide one still holds the round
        # events of whichever test ran a sim without an output_dir before
        sim = make_cifar_sim(
            compression=CLAIM_CFG,
            observability=Observability(enabled=True, output_dir=d,
                                        registry=MetricsRegistry()),
        )
        sim.fit(2)
        rounds = [
            json.loads(line)
            for line in open(os.path.join(d, "metrics.jsonl"))
        ]
        rec = [r for r in rounds if r.get("event") == "round"][0]
        assert rec["gather_bytes_wire"] < rec["gather_bytes"]
        assert rec["wire_compression_ratio"] >= 8.0


@pytest.mark.chaos
class TestRobustnessUnderCompression:
    """resilience/test_faults.py TestRobustnessClaim, re-run through the
    lossy channel: 2/8 clients at scale=-15."""

    PLAN = FaultPlan(seed=1, client_faults=(
        ClientFault(clients=(0, 1), kind="scale", scale=-15.0),
    ))
    ROUNDS = 8

    def _trajectory(self, strategy):
        hist = make_sim(
            strategy, fault_plan=self.PLAN, compression=CLAIM_CFG
        ).fit(self.ROUNDS)
        return [r.fit_losses["backward"] for r in hist]

    def test_fedavg_mean_diverges_on_lossy_updates(self):
        t = self._trajectory(FedAvg())
        assert (not all(np.isfinite(t))) or t[-1] > 2.0 * t[0], t

    def test_trimmed_mean_keeps_converging_on_lossy_updates(self):
        t = self._trajectory(
            RobustFedAvg("trimmed_mean", trim_fraction=0.25)
        )
        assert all(np.isfinite(t)), t
        assert t[-1] < t[0], t

    def test_fault_injection_identical_across_modes_under_compression(self):
        losses = {}
        for mode in ("pipelined", "chunked"):
            hist = make_sim(
                FedAvg(), fault_plan=FaultPlan(seed=3, client_faults=(
                    ClientFault(clients=(2,), kind="sign_flip",
                                probability=0.6),
                )), compression=CLAIM_CFG, execution_mode=mode,
            ).fit(4)
            losses[mode] = [r.fit_losses["backward"] for r in hist]
        assert losses["pipelined"] == losses["chunked"]
