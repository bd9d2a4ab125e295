"""What decides ``correct``, at toy size on the CPU: the system's first
rounds agree with the plain float32 reference; the reference computed one
precision step below (float8 operands), or in bfloat16 everywhere, does
not; and a run whose timed path is broken underneath comes out not correct.
"""

import time

import pytest

from benchmarks.harness import check, window
from benchmarks.harness.spec import Cell

from . import toy


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench")))


def _cells(toy_root):
    root, bm = toy_root
    return [Cell(w["name"], root=root) for w in bm["workloads"] if w["chips"] == 1]


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, where = check.worst_leaf_gap(prog, ref)
    assert where == "a" and abs(gap - 0.1) < 1e-12  # c is held to the median leaf
    assert check.worst_leaf_gap({"a": 0.0, "b": 2.0, "c": 1e-9}, ref)[0] == 1.0
    assert check.worst_leaf_gap({"a": 1.0}, ref)[0] == check.HUGE
    ok, checks = check.decide({"x": 0.5, "y": float("nan")}, {"x": 1.0, "y": 1.0})
    assert not ok and checks["x"] == {"value": 0.5, "limit": 1.0}
    assert not check.decide({"x": 0.5}, {})[0]  # no limit, no pass


def test_program_agrees_and_lower_precision_does_not(toy_root):
    for cell in _cells(toy_root):
        limits = cell.limits()
        seed = 2**31 + 5
        sim, prog = window.first_rounds(cell, seed)
        window.release(sim)
        ref = window.reference_rounds(cell, seed)
        sound = check.numbers(prog, ref)
        ok, _ = check.decide(sound, limits)
        assert ok, (cell.name, sound)
        for control in ("float8_operands", "bfloat16_everywhere"):
            low = window.reference_rounds(cell, seed, numerics=control)
            bad = check.numbers(low, ref)
            ok, checks = check.decide(bad, limits)
            assert not ok, (cell.name, control, bad)
            failing = [k for k, c in checks.items() if c["value"] > c["limit"]]
            # the control fails by a clear factor, not at the edge
            assert any(bad[k] > 3 * sound[k] for k in failing), (sound, bad)


def _server_keeps_its_state(sim):
    sim.strategy.aggregate = lambda server_state, results, round_idx: server_state


def _half_the_clients_left_out(sim):
    import jax.numpy as jnp

    orig = sim.strategy.aggregate

    def aggregate(server_state, results, round_idx):
        n = results.mask.shape[0]
        keep = (jnp.arange(n) < n // 2).astype(results.mask.dtype)
        return orig(server_state, results.replace(mask=results.mask * keep),
                    round_idx)

    sim.strategy.aggregate = aggregate


@pytest.mark.parametrize("traffic", ["toy_fedavg_seq128", "toy_chunked"])
@pytest.mark.parametrize("breaker", [_server_keeps_its_state,
                                     _half_the_clients_left_out])
def test_a_broken_timed_path_is_not_correct(toy_root, breaker, traffic):
    cell = next(c for c in _cells(toy_root) if c.workload["traffic"] == traffic)
    res = window.run_cell(cell, 11, 0.3, False, toy.fake_device(1),
                          time.perf_counter(), break_program=breaker)
    assert res["correct"] is False
    over = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert over & {"grad1_gap", "dparam_gap"}, res["checks"]
