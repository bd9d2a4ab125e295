"""The cell ``deepseek_v2.fedavg_lora_seq1024``: it resolves from its files at
the published widths, its FLOP and byte functions agree with hand counts, a
toy twin of it (the published routing: 8 groups, the best 3, 6 a token) runs
``correct`` on the CPU while the float8 control does not, planted faults in
the routing, the positions, the value width and the adapters come out not
correct, and the four readers read nothing, without raising, from a trace
that has no such scope (the parent's)."""

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

CELL = "deepseek_v2.fedavg_lora_seq1024"
TWIN = "toy_deepseek_v2.toy_fedavg_lora_seq1024"
# one dense and two expert layers at toy widths; the router whole as
# published (160 wide, 8 groups, the best 3, 6 a token) with two whole groups
# held, 40 experts, and the routed weights three times the published 16 * s.
# Both from readings of the toy on the CPU: the seeded router is flat (logits
# about N(0, 0.35^2): the configuration's ``assumed.weights``), so a chosen
# expert's weight is 0.2 at 16 * s and what a token gets from its held experts
# is small beside its stream; with 8 held and 16 * s a dropped group limit
# then reads 0.05 on the worst leaf, inside what tells bfloat16 (0.03-0.05)
# from float8 (0.18); with 40 held and 48 * s it reads 0.10-0.15
TOY_CFG = {"hidden_size": 64, "intermediate_size": 128, "vocab_size": 64,
           "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 24,
           "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "moe_intermediate_size": 32,
           "n_routed_experts": 40, "routed_scaling_factor": 48}
# as the real limits are set, from two readings of the toy on the CPU over
# three seeds (fit(1) then fit(2), like the cell): each worst-leaf limit lies
# between the program's largest (0.051 / 0.044) and the float8 control's
# smallest (0.203 / 0.191); the losses are held at three times the program's
# largest (0.0166; the control reads 0.009 to 0.064, so it is the leaves that
# fail it on every seed)
TOY_LIMITS = {"loss_r1_gap": 0.05, "loss_r2_gap": 0.05, "loss_r3_gap": 0.05,
              "grad1_gap": 0.085, "dparam_gap": 0.085}


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    root, _ = toy.make_root(str(tmp_path_factory.mktemp("deepseek")))
    bench = os.path.join(root, "benchmarks")
    path = os.path.join(bench, "configs", "toy_deepseek_v2.json")
    cfg = dict(load_json(path), **TOY_CFG)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "limits", TWIN + ".json"), "w") as f:
        json.dump(TOY_LIMITS, f)
    return Cell(TWIN, root=root)


def test_the_cell_resolves_at_the_published_widths():
    cell = Cell(CELL, root=toy.REPO)
    bm = load_json(os.path.join(toy.REPO, "BENCHMARK.json"))
    entry = next(c for c in bm["configs"] if c["name"] == "deepseek_v2")
    assert entry["reduced"] == cell.cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.cfg["published"] == {"num_hidden_layers": 60,
                                     "n_routed_experts": 160,
                                     "vocab_size": 102400}
    assert (cell.cfg["router_width"], cell.cfg["first_expert_held"]) == (160, 0)
    ref = load_module("reference", cell.family)
    spec = ref.param_spec(cell.cfg, cell.job)
    count = lambda keep: sum(math.prod(s) for k, (s, _) in spec.items()  # noqa: E731
                             if keep(k))
    trains = lambda k: bool({"lora_a", "lora_b", "score"} & set(k.split("/")))  # noqa: E731
    mla = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
           + 128 * 128 * 5120 + 1536 + 512)  # with its two inner norms
    expert = 3 * 5120 * 1536
    base = (5 * (mla + 2 * 5120)  # every layer's attention and two norms
            + 3 * 5120 * 12288  # layer 0's dense SwiGLU
            + 4 * (5120 * 160 + 8 * expert + 3 * 5120 * 3072)
            + 12800 * 5120 + 5120)
    assert count(lambda k: not trains(k)) == base and 1.94e9 < base < 1.95e9
    lora = lambda n_in, n_out: 8 * (n_in + n_out)  # noqa: E731
    mla_lora = (lora(5120, 1536) + lora(1536, 24576) + lora(5120, 576)
                + lora(512, 32768) + lora(16384, 5120))
    mlp_lora = lambda f: 2 * lora(5120, f) + lora(f, 5120)  # noqa: E731
    assert count(trains) == (5 * mla_lora + mlp_lora(12288) + 4 * mlp_lora(3072)
                             + 5120 * 4)
    assert 4.9e6 < count(trains) < 5.0e6
    # every expert's matrices are leaves of their own, never a stack
    assert spec["layers_4/mlp/experts_7/down_proj/kernel"][0] == (1536, 5120)
    assert not any("experts_" in k and trains(k) for k in spec)
    frozen = [k for k in spec if not trains(k)]
    assert len(frozen) > len(spec) / 2  # so the median leaf's change is 0
    job = cell.job
    assert (job["clients"], job["batch"], job["local_steps"],
            job["data"]["seq"], job["data"]["min_len_frac"]) == (4, 1, 2, 1024,
                                                                 0.5)
    assert job["train_examples"] == [4, 6, 8, 10] and job["val_examples"] == 2
    assert job["strategy"] == {"name": "fedavg_adapters",
                               "trainable": ["lora_a", "lora_b", "score"]}
    assert job["optimizer"] == {"name": "sgd", "lr": 0.0005}
    assert (job["rounds_per_fit"], job["check_calls"], job["remat"],
            job["mesh"]) == (6, [1, 2], True, None)
    assert job["attention"]["kind"] == "flash"
    assert load_module("families", cell.family).build_module
    assert cell.compute_dtype == "bfloat16" and cell.chips == 1
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "mla_attention_ms_per_round", "mla_flash_roofline_pct",
        "moe_ms_per_round", "moe_experts_roofline_pct", "fit_prologue_ms",
        "device_idle_pct"}
    # the catalog's keys, but for the three reduced, at their published values
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "DeepSeek-V2")["config"]
        assert {k for k, v in row.items() if cell.cfg.get(k) != v} == set(
            cell.cfg["reduced"])


def test_step_flops_and_kernel_bytes_by_hand():
    cell = Cell(CELL, root=toy.REPO)
    f = load_module("flops", "deepseek_v2_classifier")
    mla = (2 * 5120 * 1536 + 2 * 1536 * 24576 + 2 * 5120 * 576
           + 2 * 512 * 32768 + 2 * 16384 * 5120 + 1024 * 128 * 192
           + 1024 * 128 * 128)
    assert f.mla_flops_per_token(cell.cfg, 1024) == mla
    assert f.dense_mlp_flops_per_token(cell.cfg) == 6 * 5120 * 12288
    assert f.expected_local_assignments(cell.cfg) == pytest.approx(0.3)
    routed = 0.3 * 6 * 5120 * 1536
    assert f.routed_flops_per_token(cell.cfg) == pytest.approx(routed)
    layer = 2 * 5120 * 160 + 6 * 5120 * 3072 + routed
    forward = 5 * mla + 6 * 5120 * 12288 + 4 * layer
    assert f.forward_flops_per_token(cell.cfg, 1024) == pytest.approx(forward)
    # forward and activation gradients, not 3 x: 5.16 TFLOP a client step
    step = f.train_step_flops(cell.cfg, cell.job)
    assert step == pytest.approx(2 * forward * 1024)
    assert abs(step / 1e12 - 5.16) < 0.005
    assert abs(f.routed_share(cell.cfg, cell.job) - 0.0225) < 0.0005
    m = load_module("flops", "mla_flash")
    assert m.call_flops("fwd", 128, 1024, 192, 128) == 2 * 1024**2 * 128 * 320
    assert m.call_flops("dq", 128, 1024, 192, 128) == 2 * 1024**2 * 128 * 512
    assert m.call_flops("dkv", 128, 1024, 192, 128) == 2 * 1024**2 * 128 * 640
    assert m.call_bytes("fwd", 128, 1024, 192, 128) == 128 * 1024 * (
        (2 * 192 + 2 * 128) * 2 + 8)
    assert m.causal_fraction(1024) == 1025 / 2048
    fwd = m.least_seconds("fwd", 128, 1024, 192, 128, 197e12, 819e9,
                          executed=1025 / 2048)
    assert fwd[1] == "compute" and fwd[0] == pytest.approx(
        2 * 1024**2 * 128 * 320 * 1025 / 2048 / 197e12)
    # two forwards, dQ and dK/dV a layer and step; 5 layers, 8 steps a round
    per_round = m.least_seconds_per_round(cell.cfg, cell.job, 197e12, 819e9)
    assert per_round == pytest.approx(
        40 * 2 * 1024**2 * 128 * (2 * 320 + 512 + 640) * 1025 / 2048 / 197e12)
    e = load_module("flops", "moe_experts")
    assert e.expected_rows_per_expert(cell.cfg, 4096) == pytest.approx(153.6)
    assert e.step_flops(cell.cfg, 4096) == pytest.approx(
        2 * 8 * 153.6 * 6 * 5120 * 1536)
    assert e.step_bytes(cell.cfg, 4096) == pytest.approx(
        2 * 8 * 3 * 5120 * 1536 * 2 + 5 * 8 * 153.6 * 5120 * 2)
    secs, bound = e.least_seconds_per_round(cell.cfg, cell.job, 197e12, 819e9)
    # four expert layers x two local steps, the clients folded into one call
    assert bound == "memory" and secs == pytest.approx(
        8 * e.step_bytes(cell.cfg, 4096) / 819e9)


def test_toy_twin_is_correct_and_its_float8_control_is_not(twin):
    limits = twin.limits()
    for seed in (2**31 + 5, 2**31 + 6):
        sim, prog = window.first_rounds(twin, seed)
        shared = sim.strategy.shared_params(sim.server_state)
        gauges = {e["event"]: e for e in sim.observability.registry.events
                  }["parameter_split"]
        window.release(sim)
        assert (gauges["moe_experts_held"], gauges["moe_experts_total"],
                gauges["moe_assignment_rows_bound"]) == (40, 160, 4 * 4 * 32 * 6)
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


def _replaced_dims(**changes):
    def fault(monkeypatch):
        ds = importlib.import_module("fl4health_tpu.models.deepseek")
        real = ds.route
        monkeypatch.setattr(ds, "route", lambda p, u, dims: real(
            p, u, dataclasses.replace(dims, **{
                k: (v(dims) if callable(v) else v)
                for k, v in changes.items()})))
    return fault


def _rope_off(monkeypatch):
    ds = importlib.import_module("fl4health_tpu.models.deepseek")
    monkeypatch.setattr(ds, "apply_rope", lambda x, cos, sin: x)


def _value_width_padded_wrongly(monkeypatch):
    """v padded to the key's width at the FRONT: the first value lanes of
    the output are the padding's."""
    import jax.numpy as jnp

    fa = importlib.import_module("fl4health_tpu.kernels.flash_attention")
    real = fa._fwd_call

    def call(q, k, v, *rest):
        out, lse = real(q, k, jnp.roll(v, v.shape[-1] // 4, axis=-1), *rest)
        return out, lse

    monkeypatch.setattr(fa, "_fwd_call", call)


def _adapters_at_half_scale(monkeypatch):
    ds = importlib.import_module("fl4health_tpu.models.deepseek")
    common = importlib.import_module("fl4health_tpu.models.decoder_common")
    real = common.lora_dense
    half = lambda p, x, dims: real(p, x, dataclasses.replace(  # noqa: E731
        dims, lora_scale=dims.lora_scale / 2))
    monkeypatch.setattr(common, "lora_dense", half)
    monkeypatch.setattr(ds, "lora_dense", half)


FAULTS = {
    "group limit dropped": _replaced_dims(topk_group=lambda d: d.n_group),
    "routed_scaling_factor 1": _replaced_dims(routed_scale=1.0),  # of 48
    "RoPE off": _rope_off,
    "value width padded wrongly": _value_width_padded_wrongly,
    "adapters at half scale": _adapters_at_half_scale,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_program_is_not_correct(twin, monkeypatch,
                                                       fault):
    """What the limits are for: the program with one thing wrong in the
    routing, the positions, the kernels' second width or the adapters comes
    out not correct, through the harness's own comparison."""
    FAULTS[fault](monkeypatch)
    seed = 2**31 + 5
    sim, prog = window.first_rounds(twin, seed)
    window.release(sim)
    ok, checks = check.decide(
        check.numbers(prog, window.reference_rounds(twin, seed)),
        twin.limits())
    assert not ok, checks


def test_a_trace_without_the_scopes_reads_none(tmp_path):
    """The parent's program has none of the four scopes: every reader returns
    None and the result line leaves the metric out."""
    root = str(tmp_path)
    fixture = os.path.join(toy.REPO, "benchmarks", "fixtures",
                           "trace_spans_small.xplane.pb.xz")
    folder = os.path.join(root, ".bench_cache", "trace", "old", "plugins",
                          "profile", "fixture")
    os.makedirs(folder)
    path = os.path.join(folder, "host.xplane.pb")
    with lzma.open(fixture) as f, open(path, "wb") as out:
        out.write(f.read())
    trace = trace_reduce.load(path)
    real = Cell(CELL, root=toy.REPO)
    ctx = {"trace": trace, "rounds": 3,
           "cell": types.SimpleNamespace(root=root, name="old",
                                         bench_dir=BENCH_DIR, cfg=real.cfg,
                                         job=real.job),
           "dev": types.SimpleNamespace(hbm_bytes_per_s=819e9,
                                        bf16_flops_per_s=197e12)}
    names = ("mla_attention_ms_per_round", "mla_flash_roofline_pct",
             "moe_ms_per_round", "moe_experts_roofline_pct")
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None
    # and no file at all reads None too
    ctx["cell"] = types.SimpleNamespace(root=os.path.join(root, "nowhere"),
                                        name="old", bench_dir=BENCH_DIR)
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None
