"""The cell ``jamba2_3b.fedavg_lora_seq2048``: it resolves from its files at
the published widths, its FLOP and byte functions agree with hand counts, a
toy twin of it (every kind of layer present) runs ``correct`` on the CPU
while the float8 control does not, frozen leaves compare exactly, and the
three ``fl_layer::`` readers read a small recorded trace of it."""

import dataclasses
import importlib
import json
import lzma
import math
import os
import time
import types

import pytest

from benchmarks import trace_reduce
from benchmarks.harness import check, window
from benchmarks.harness.spec import BENCH_DIR, Cell, load_json, load_module

from . import toy

CELL = "jamba2_3b.fedavg_lora_seq2048"
TWIN = "toy_jamba2_3b.toy_fedavg_lora_seq2048"
# every kind of layer in four: Mamba, Mamba, attention, Mamba
TOY_CFG = {"hidden_size": 64, "intermediate_size": 128, "vocab_size": 64,
           "num_hidden_layers": 4, "num_attention_heads": 4,
           "attn_layer_period": 4, "attn_layer_offset": 2,
           "mamba_d_state": 4, "mamba_dt_rank": 4}
# as the real limits are set, from two readings of the toy on the CPU over
# five seeds (fit(1) then fit(2), like the cell): each worst-leaf limit lies
# between the program's largest (0.023 / 0.026) and the float8 control's
# smallest (0.23 / 0.28); the losses are held at three times the program's
# largest (0.0026; the control reads 0.0015 to 0.035, so it is the leaves
# that fail it)
TOY_LIMITS = {"loss_r1_gap": 0.008, "loss_r2_gap": 0.008, "loss_r3_gap": 0.008,
              "grad1_gap": 0.08, "dparam_gap": 0.08}


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    root, _ = toy.make_root(str(tmp_path_factory.mktemp("jamba")))
    bench = os.path.join(root, "benchmarks")
    path = os.path.join(bench, "configs", "toy_jamba2_3b.json")
    cfg = dict(load_json(path), **TOY_CFG)
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "limits", TWIN + ".json"), "w") as f:
        json.dump(TOY_LIMITS, f)
    return Cell(TWIN, root=root)


def test_the_cell_resolves_at_the_published_widths():
    cell = Cell(CELL, root=toy.REPO)
    bm = load_json(os.path.join(toy.REPO, "BENCHMARK.json"))
    entry = next(c for c in bm["configs"] if c["name"] == "jamba2_3b")
    assert entry["reduced"] == cell.cfg["reduced"] == ["num_hidden_layers"]
    assert cell.cfg["published"] == {"num_hidden_layers": 28}
    ref = load_module("reference", cell.family)
    spec = ref.param_spec(cell.cfg, cell.job)
    count = lambda keep: sum(math.prod(s) for k, (s, _) in spec.items()  # noqa: E731
                             if keep(k))
    trains = lambda k: bool({"lora_a", "lora_b", "score"} & set(k.split("/")))  # noqa: E731
    # one Mamba layer 41.2 M + MLP 62.9 M; the attention layer 13.8 M + MLP
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 + 16 + 16
             + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    mlp = 3 * 2560 * 8192
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    base = (13 * (mamba + mlp) + attn + mlp + 14 * 2 * 2560  # layer norms
            + 65536 * 2560 + 2560)
    assert count(lambda k: not trains(k)) == base and 1.59e9 < base < 1.61e9
    # rank-8 adapters: 13 Mamba layers, one attention layer, and the head
    lora = lambda n_in, n_out: 8 * (n_in + n_out)  # noqa: E731
    mlp_lora = 2 * lora(2560, 8192) + lora(8192, 2560)
    per_mamba = (lora(2560, 10240) + lora(5120, 192) + lora(5120, 2560)
                 + mlp_lora)
    per_attn = lora(2560, 2560) + 2 * lora(2560, 128) + mlp_lora
    assert count(trains) == 13 * per_mamba + per_attn + 2560 * 4
    assert 6.3e6 < count(trains) < 6.5e6
    frozen = [k for k in spec if not trains(k)]
    assert len(frozen) > len(spec) / 2  # so the median leaf's change is 0
    job = cell.job
    assert (job["clients"], job["batch"], job["local_steps"],
            job["data"]["seq"]) == (4, 1, 2, 2048)
    assert job["strategy"]["name"] == "fedavg_adapters"
    assert job["attention"] == {"kind": "flash", "block_q": 512, "block_k": 512}
    assert load_module("families", cell.family).build_module
    assert load_module("strategies", "fedavg_adapters").build
    assert load_module("reference/strategies", "fedavg_adapters").run
    assert cell.compute_dtype == "bfloat16" and cell.chips == 1
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "ssm_scan_ms_per_round", "ssm_scan_roofline_pct",
        "shared_cast_ms_per_round", "fit_prologue_ms", "device_idle_pct"}


def test_step_flops_and_scan_bytes_by_hand():
    cell = Cell(CELL, root=toy.REPO)
    f = load_module("flops", "jamba_classifier")
    mixer = 2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * 2560
    mlp = 3 * 2 * 2560 * 8192
    attn = 2 * 2 * 2560 * 2560 + 2 * 2 * 2560 * 128 + 2 * 2048 * 2560
    assert f.mixer_flops_per_token(cell.cfg) == mixer == 82247680
    assert f.mlp_flops_per_token(cell.cfg) == mlp == 125829120
    assert f.attention_flops_per_token(cell.cfg, 2048) == attn
    forward = 13 * (mixer + mlp) + attn + mlp
    assert f.forward_flops_per_token(cell.cfg, 2048) == forward
    assert abs(forward / 1e9 - 2.87) < 0.005
    # forward and activation gradients, not 3 x: 11.75 TFLOP a client step
    assert f.train_step_flops(cell.cfg, cell.job) == 2 * forward * 2048
    assert abs(f.train_step_flops(cell.cfg, cell.job) / 1e12 - 11.75) < 0.01
    assert abs(f.mixer_share(cell.cfg, cell.job) - 0.373) < 0.002
    s = load_module("flops", "selective_scan")
    big, small = 2048 * 5120 * 2, 2048 * 16 * 4
    assert s.pass_bytes("fwd", 2048, 5120, 16) == 4 * big + 2 * small
    assert s.pass_bytes("bwd", 2048, 5120, 16) == 7 * big + 4 * small
    assert s.passes_per_step(True) == {"fwd": 2, "bwd": 1}
    assert s.mamba_layers(cell.cfg) == 13
    per_step = 2 * (4 * big + 2 * small) + 7 * big + 4 * small
    least = s.least_seconds_per_round(cell.cfg, cell.job, 819e9)
    assert abs(least - per_step * 13 * 8 / 819e9) < 1e-12
    assert 0.03 < least < 0.05  # 40 ms a round at the HBM peak


def test_toy_twin_is_correct_and_its_float8_control_is_not(twin):
    limits = twin.limits()
    for seed in (2**31 + 5, 2**31 + 6):
        sim, prog = window.first_rounds(twin, seed)
        shared = sim.strategy.shared_params(sim.server_state)
        window.release(sim)
        ref = window.reference_rounds(twin, seed)
        sound = check.numbers(prog, ref)
        assert check.decide(sound, limits)[0], (seed, sound)
        # frozen leaves compare exactly: 0 on both sides
        frozen = [k for k, v in ref["snapshots"][-1].items() if v == 0.0]
        assert len(frozen) > len(ref["snapshots"][-1]) / 2 and shared
        assert all(prog["snapshots"][-1][k] == 0.0 for k in frozen)
        low = window.reference_rounds(twin, seed, numerics="float8_operands")
        bad = check.numbers(low, ref)
        ok, checks = check.decide(bad, limits)
        assert not ok, (seed, bad)
        failing = [k for k, c in checks.items() if c["value"] > c["limit"]]
        assert any(bad[k] > 3 * sound[k] for k in failing), (sound, bad)


def test_toy_twin_runs_through_the_harness(twin):
    res = window.run_cell(twin, 2**31 + 77, 0.5, False, toy.fake_device(1),
                          time.perf_counter())
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["metrics"]) == {m["name"]
                                    for m in twin.metrics("end_to_end")}


def test_a_base_leaf_that_drifts_is_not_correct(twin):
    """One ulp on one frozen leaf reads HUGE: its reference change is 0."""
    import jax.numpy as jnp

    def nudge(sim):
        params = sim.global_params
        leaf = params["layers_0"]["mamba"]["D"]
        params["layers_0"]["mamba"]["D"] = jnp.nextafter(leaf, leaf + 1)
        sim.set_global_params(params)

    res = window.run_cell(twin, 11, 0.3, False, toy.fake_device(1),
                          time.perf_counter(), break_program=nudge)
    assert res["correct"] is False
    assert res["checks"]["grad1_gap"]["value"] > 1e6


def _wrong_scan_backward(monkeypatch):
    """dB a third too large out of the backward kernel's call."""
    # (the package exports a function of the module's name over it)
    ss = importlib.import_module("fl4health_tpu.kernels.selective_scan")
    real = ss._bwd_call

    def call(*args):
        out = list(real(*args))
        out[3] = out[3] * 1.3
        return tuple(out)

    monkeypatch.setattr(ss, "_bwd_call", call)


def _flash_without_the_causal_mask(monkeypatch):
    fa = importlib.import_module("fl4health_tpu.kernels.flash_attention")
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: real(
        *a, **{**kw, "causal": False}))


def _adapters_at_half_scale(monkeypatch):
    from fl4health_tpu.models import jamba

    real = jamba.lora_dense
    monkeypatch.setattr(jamba, "lora_dense", lambda p, x, dims: real(
        p, x, dataclasses.replace(dims, lora_scale=dims.lora_scale / 2)))


@pytest.mark.parametrize("fault", [_wrong_scan_backward,
                                   _flash_without_the_causal_mask,
                                   _adapters_at_half_scale])
def test_a_planted_fault_in_the_program_is_not_correct(twin, monkeypatch,
                                                       fault):
    """What the limits are for: the program with one thing wrong in the scan's
    backward pass, the flash mask or the adapters comes out not correct,
    through the harness's own comparison."""
    fault(monkeypatch)
    seed = 2**31 + 5
    sim, prog = window.first_rounds(twin, seed)
    window.release(sim)
    ok, checks = check.decide(
        check.numbers(prog, window.reference_rounds(twin, seed)),
        twin.limits())
    assert not ok, checks


# -- the three fl_layer:: readers on a recorded trace of the cell ----------
FIXTURE = os.path.join(toy.REPO, "benchmarks", "fixtures",
                       "trace_jamba_small.xplane.pb.xz")
# what the fixture ran (.scratch/make_fixture.py of PR 27, on a v5e): the
# cell at real widths cut to Mamba, attention, Mamba, a 4,096-row vocabulary,
# 512 positions, one 2-round fit(), Python tracer off
FIXTURE_CFG = {"num_hidden_layers": 3, "attn_layer_period": 3,
               "attn_layer_offset": 1, "vocab_size": 4096}
FIXTURE_ROUNDS, FIXTURE_SEQ = 2, 512


def _unpack(fixture, root, cell):
    folder = os.path.join(root, ".bench_cache", "trace", cell, "plugins",
                          "profile", "fixture")
    os.makedirs(folder)
    path = os.path.join(folder, "host.xplane.pb")
    with lzma.open(fixture) as f, open(path, "wb") as out:
        out.write(f.read())
    return path


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("jamba_trace"))
    real = Cell(CELL, root=toy.REPO)
    cell = types.SimpleNamespace(
        root=root, name=CELL, bench_dir=BENCH_DIR,
        cfg=dict(real.cfg, **FIXTURE_CFG),
        job=dict(real.job, data=dict(real.job["data"], seq=FIXTURE_SEQ)))
    trace = trace_reduce.load(_unpack(FIXTURE, root, CELL))
    dev = types.SimpleNamespace(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    return {"trace": trace, "rounds": FIXTURE_ROUNDS, "cell": cell, "dev": dev}


def test_the_three_readers_read_the_recorded_trace(traced):
    read = lambda name: load_module("layer_metrics", name).read(traced)  # noqa: E731
    scan_ms = read("ssm_scan_ms_per_round")
    cast_ms = read("shared_cast_ms_per_round")
    share = read("ssm_scan_roofline_pct")
    assert scan_ms > 0 and cast_ms > 0 and 0 < share <= 100
    common = load_module("layer_metrics", "layer_common")
    by_layer = common._of_run(traced["trace"], trace_reduce.find_xplane(
        os.path.join(traced["cell"].root, ".bench_cache", "trace", CELL)))
    # the scopes nest: the scan lies inside the mixer
    assert by_layer["mamba_mixer"] > by_layer["ssm_scan"] > 0
    assert by_layer["attention"] > 0 and by_layer["shared_cast"] > 0
    assert scan_ms == pytest.approx(by_layer["ssm_scan"] * 1e3 / FIXTURE_ROUNDS)
    least = load_module("flops", "selective_scan").least_seconds_per_round(
        traced["cell"].cfg, traced["cell"].job, 819e9)
    assert share == pytest.approx(100 * least * FIXTURE_ROUNDS
                                  / by_layer["ssm_scan"])
    # both Mosaic calls of the scan are in the trace under their names
    lane = traced["trace"].devices[sorted(traced["trace"].devices)[0]]
    names = " ".join({e.name for e in lane.ops if "tpu_custom_call" in e.name})
    assert "ssm_scan_fwd" in names and "ssm_scan_bwd" in names


def test_a_trace_without_the_scopes_reads_none(tmp_path):
    """The parent's program has no ``fl_layer::`` scope: every reader returns
    None and the result line leaves the metric out."""
    root = str(tmp_path)
    fixture = os.path.join(toy.REPO, "benchmarks", "fixtures",
                           "trace_spans_small.xplane.pb.xz")
    trace = trace_reduce.load(_unpack(fixture, root, "old"))
    real = Cell(CELL, root=toy.REPO)
    ctx = {"trace": trace, "rounds": 3,
           "cell": types.SimpleNamespace(root=root, name="old",
                                         bench_dir=BENCH_DIR, cfg=real.cfg,
                                         job=real.job),
           "dev": types.SimpleNamespace(hbm_bytes_per_s=819e9)}
    for name in ("ssm_scan_ms_per_round", "ssm_scan_roofline_pct",
                 "shared_cast_ms_per_round"):
        assert load_module("layer_metrics", name).read(ctx) is None
    # and no file at all reads None too
    ctx["cell"] = types.SimpleNamespace(root=os.path.join(root, "nowhere"),
                                        name="old", bench_dir=BENCH_DIR)
    assert load_module("layer_metrics", "ssm_scan_ms_per_round").read(ctx) is None


def test_layer_scopes_are_read_from_a_name_stack():
    common = load_module("layer_metrics", "layer_common")
    stack = ("jit(fit_round)/vmap(fl_stage::local_train)/while/body/"
             "checkpoint/fl_layer::mamba_mixer/fl_layer::ssm_scan/ssm_scan_fwd:")
    assert common.layers_of(stack) == {"mamba_mixer", "ssm_scan"}
    assert common.layers_of("jit(fit_round)/transpose(jvp(fl_layer::attention))"
                            "/dot_general:") == {"attention"}
    assert common.layers_of("jit(fit_round)/fl_stage::server_update/add:") == set()
    assert common.layers_of(None) == set()
