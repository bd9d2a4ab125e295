"""chip_smoke.py's own control flow, at toy size on the CPU.

The script is the first command of every chip session and the driver's
acceptance check, so what it does when something is wrong matters as much
as what it does when all is well: no TPU -> non-zero and no result line; a
stage that raises, or a fit() whose introspection quietly failed -> the run
fails. The stages themselves run here through the same functions the chip
run uses, with ``TOY`` shapes and interpret-mode kernels.
"""

import importlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cs():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module("chip_smoke")


@pytest.fixture
def no_cache_move(monkeypatch):
    """main() places the compile cache; the test process keeps its own."""
    from fl4health_tpu.utils import runtime

    monkeypatch.setattr(runtime, "configure_compile_cache",
                        lambda *a, **kw: "unchanged")


CTX = {"platform": "cpu"}


class TestDeviceGate:
    def test_cpu_without_rehearsal_exits_nonzero_and_prints_no_result(
        self, cs, no_cache_move, capsys
    ):
        with pytest.raises(SystemExit) as e:
            cs.main([])
        assert e.value.code not in (0, None)
        out = capsys.readouterr().out
        assert "CHIP_SMOKE FAILED: device gate" in out
        assert "platform=cpu" in out
        assert '"ok"' not in out

    def test_rehearsal_is_explicit_and_says_so(self, cs, capsys):
        device = cs.device_gate(rehearsal=True)
        assert device["platform"] == "cpu"
        assert "REHEARSAL" in capsys.readouterr().out


class TestFailuresFailTheRun:
    def test_a_raising_stage_gives_nonzero_and_no_result_line(
        self, cs, no_cache_move, monkeypatch, capsys
    ):
        def boom(sz, ctx):
            raise RuntimeError("Mosaic said no")

        monkeypatch.setitem(cs.STAGE_FNS, "kernels", boom)
        assert cs.main(["--rehearsal", "--stages", "kernels"]) == 1
        out = capsys.readouterr().out
        assert "stage kernels: FAILED" in out and "Mosaic said no" in out
        assert "CHIP_SMOKE FAILED: kernels" in out
        assert '"ok"' not in out

    def test_partial_or_rehearsal_runs_print_no_result_line(
        self, cs, no_cache_move, monkeypatch, capsys
    ):
        monkeypatch.setitem(cs.STAGE_FNS, "cnn", lambda sz, ctx: "stubbed")
        assert cs.main(["--rehearsal", "--stages", "cnn"]) == 0
        out = capsys.readouterr().out
        assert "CHIP_SMOKE REHEARSAL OK" in out
        assert '"ok"' not in out

    def test_missing_introspection_report_is_a_failure(self, cs):
        """fit() only logs when build-time introspection fails; the smoke
        must not pass a run whose reports are absent."""
        sim, _ = cs.cnn_sim(cs.TOY)
        sim.observability.introspection = False
        with pytest.raises(cs.SmokeFailure, match="introspection report"):
            cs.run_fit(sim, 3, "cpu")

    def test_trajectory_tolerances(self, cs):
        ref = [10.0, 9.0, 8.0]
        assert cs.require_same_trajectory(
            [10.0, 9.0, 8.0 + 4e-5], ref, cs._rtols(False, 3), "x"
        ) == pytest.approx(5e-6)
        with pytest.raises(cs.SmokeFailure, match="relative diffs"):
            cs.require_same_trajectory(
                [10.0, 9.0, 8.001], ref, cs._rtols(False, 3), "x")
        # bf16: tight in the first round, loose after
        cs.require_same_trajectory(
            [10.005, 9.5, 8.5], ref, cs._rtols(True, 3), "x")
        with pytest.raises(cs.SmokeFailure):
            cs.require_same_trajectory(
                [10.05, 9.0, 8.0], ref, cs._rtols(True, 3), "x")


class TestStagesAtToySize:
    """The stage functions the chip run uses, on TOY shapes."""

    def test_encoder_both_modes_agree(self, cs):
        ctx = dict(CTX)
        detail = cs.stage_encoder(cs.TOY, ctx)
        assert len(ctx["encoder_fit_losses"]) == 3
        assert "modes_max_rel_diff" in detail

    def test_long_context_runs_the_kernel_in_interpret_mode(self, cs):
        assert "tpu_custom_call=False" in cs.stage_long_context(
            cs.TOY, dict(CTX))

    def test_kernel_checks(self, cs):
        assert "checks" in cs.stage_kernels(cs.TOY, dict(CTX))

    def test_cnn(self, cs):
        assert "conv_impl=lax" in cs.stage_cnn(cs.TOY, dict(CTX))

    @pytest.mark.slow
    def test_mesh_stages(self, cs, eight_devices):
        ctx = dict(CTX)
        cs.stage_encoder(cs.TOY, ctx)
        for name in ("mesh_encoder", "mesh_cnn", "mesh_flash", "mesh_zero1"):
            assert cs.STAGE_FNS[name](cs.TOY, ctx)
