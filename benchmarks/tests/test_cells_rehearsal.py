"""Every cell of BENCHMARK.json, at toy size on the CPU: it builds from its
two data files alone, runs through the harness, the result has the
contract's keys, and a toy configuration, traffic mix, strategy, optimizer,
end-to-end and per-layer metric come in as new files only."""

import json
import math
import os
import subprocess
import sys
import time

import pytest

from benchmarks.harness import window
from benchmarks.harness.spec import BENCH_DIR, Cell, load_json, load_module

from . import toy

REPO = toy.REPO
REAL = load_json(os.path.join(REPO, "BENCHMARK.json"))
REAL_CELLS = [w["name"] for w in REAL["workloads"]]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("name", REAL_CELLS)
def test_real_cell_resolves_from_its_two_files(name):
    cell = Cell(name, root=REPO)
    ref = load_module("reference", cell.family)
    spec = ref.param_spec(cell.cfg, cell.job)
    n_params = sum(math.prod(s) for s, _ in spec.values())
    flops = load_module("flops", cell.family).train_step_flops(cell.cfg, cell.job)
    assert n_params > 5e5 and flops > 1e9
    # the configuration's own count: leaf for leaf what the program's module
    # of this family holds at these sizes (an abstract init, nothing built)
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import build

    module = load_module("families", cell.family).build_module(cell.cfg,
                                                               cell.job)
    inp = ref.input_spec(cell.cfg, cell.job)
    x = jax.ShapeDtypeStruct((int(cell.job["batch"]), int(inp["seq"])),
                             jnp.int32)
    held = build.flatten(jax.eval_shape(
        lambda k, a: module.init(k, a, train=False), jax.random.PRNGKey(0),
        x)["params"])
    assert ({k: tuple(v.shape) for k, v in held.items()}
            == {k: tuple(shape) for k, (shape, _) in spec.items()})
    if cell.workload["config"] == "encoder_base":
        # BERT-base body at the published vocabulary: 85.05 M in the blocks,
        # 23.44 M token embeddings, positions, final LN and head
        pos = int(cell.job.get("max_positions") or 512)
        assert n_params == (85054464 + 30522 * 768 + pos * 768 + 2 * 768
                            + 768 * 4 + 4)
    assert (cell.chips == 4) == bool(cell.job.get("mesh"))
    assert load_module("families", cell.family).build_module
    assert load_module("strategies", cell.strategy["name"]).build
    assert load_module("optimizers", cell.optimizer["name"]).build_tx
    assert load_module("reference/strategies", cell.strategy["name"]).run
    assert load_module("reference/optimizers", cell.optimizer["name"]).update
    assert "precision" not in cell.job and cell.compute_dtype == "bfloat16"


@pytest.mark.parametrize("name", REAL_CELLS)
def test_toy_twin_runs_and_has_the_contracts_keys(toy_root, name):
    root, bm = toy_root
    twin = next(w["name"] for w, r in zip(bm["workloads"], REAL["workloads"])
                if r["name"] == name)
    cell = Cell(twin, root=root)
    res = window.run_cell(cell, 2**31 + 77, 0.5, False,
                          toy.fake_device(cell.chips), time.perf_counter())
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= cell.job["rounds_per_fit"]
    want = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for metric, m in line["metrics"].items():
        # the CPU backend reports no memory statistics
        assert (m["value"] > 0 or metric == "peak_hbm_gib") and m["unit"]
    for c in line["checks"].values():
        assert c["limit"] is not None and c["value"] <= c["limit"]


def test_the_default_path_cell_runs_the_chunked_scan_or_fails(toy_root):
    root, bm = toy_root
    chunked = next(w for w in bm["workloads"] if w["traffic"] == "toy_chunked")
    cell = Cell(chunked["name"], root=root)
    res = window.run_cell(cell, 2**31 + 78, 0.3, False, toy.fake_device(1),
                          time.perf_counter())
    # a call returns its rounds at once: no honest round tail, none reported
    assert res["correct"] is True and "round_ms_p95" not in res["metrics"]
    assert res["attempted"] % cell.job["rounds_per_fit"] == 0
    cell.job = dict(cell.job, execution_mode="pipelined")  # what a demotion does
    with pytest.raises(RuntimeError, match="execution mode resolved"):
        window.run_cell(cell, 5, 0.2, False, toy.fake_device(1),
                        time.perf_counter())


def test_new_cells_strategies_and_metrics_are_new_files_only(toy_root):
    root, bm = toy_root
    bench = os.path.join(root, "benchmarks")
    for sub, _, files in os.walk(BENCH_DIR):
        rel = os.path.relpath(sub, BENCH_DIR)
        if rel.split(os.sep)[0] in ("tests", "fixtures", "__pycache__"):
            continue
        for f in files:
            if f.endswith((".py", ".json")):
                with open(os.path.join(sub, f)) as a, \
                        open(os.path.join(bench, rel, f)) as b:
                    assert a.read() == b.read(), f"{rel}/{f} was edited"
    cell = Cell(bm["workloads"][0]["name"], root=root)
    assert "toy_rounds" in {m["name"] for m in cell.metrics("per_layer")}
    reader = load_module("layer_metrics", "toy_rounds", cell.bench_dir)
    assert reader.read({"rounds": 7}) == 7.0 and reader.read({"rounds": 0}) is None


def test_a_second_strategy_and_optimizer_run_from_new_files_alone(toy_root):
    """Server momentum (the program's FedOpt) over clients on SGD with
    momentum, each with a plain reference of its own: the harness finds all
    four by the names in the traffic file, and the comparison holds them to
    the same limits. A reference that ignores the server's momentum, or the
    clients', does not pass."""
    root, bm = toy_root
    name = next(w["name"] for w in bm["workloads"]
                if w["traffic"] == "toy_fedavgm")
    cell = Cell(name, root=root)
    assert cell.strategy["name"] == "toy_fedavgm"
    res = window.run_cell(cell, 2**31 + 91, 0.3, False, toy.fake_device(1),
                          time.perf_counter())
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["toy_rounds_per_s"]["value"] > 0
    assert set(res["checks"]) >= {"loss_r3_gap", "grad1_gap", "dparam_gap"}
    from benchmarks.harness import check

    seed = 2**31 + 91
    sim, prog = window.first_rounds(cell, seed)
    window.release(sim)
    for forget in ("strategy", "optimizer"):
        plain = Cell(name, root=root)
        setattr(plain, forget, {"name": {"strategy": "fedavg",
                                         "optimizer": "sgd"}[forget],
                                "lr": cell.optimizer["lr"]})
        ref = window.reference_rounds(plain, seed)
        ok, checks = check.decide(check.numbers(prog, ref), cell.limits())
        assert not ok, (forget, checks)


def _run_command(cwd, extra_env=None):
    cmd = REAL["command"] + ["--workload", REAL_CELLS[0], "--seed", "3",
                             "--seconds", "1", "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def test_no_tpu_no_result_line():
    p = _run_command(REPO)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "not 'tpu'" in p.stderr


def test_bare_directory_refuses(tmp_path):
    import shutil

    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run_command(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
