"""The cell ``trinity_mini.fedavg_lora_seq8192``: it resolves from its files
at the published widths, its FLOP and byte functions agree with hand counts,
a toy twin of it (both kinds of attention layer, a window shorter than the
sequence, grouped key/value heads through the flash calls, 24 of 40 experts
held, 6 a token) runs ``correct`` on the CPU while the float8 control does
not, planted faults in the window, the positions and the gate come out not
correct (and a bfloat16 router where the configuration states float32), and
the two new readers read nothing, without raising, from a trace that has
no such scope (the parent's)."""

import copy
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

CELL = "trinity_mini.fedavg_lora_seq8192"
TWIN = "toy_trinity_mini.toy_fedavg_lora_seq8192"
S, F = "sliding_attention", "full_attention"
# one leading dense layer and a period SSSF with a sliding layer after it, at
# toy widths: 4 query heads of 128 lanes over 2 key/value heads (the grouped addressing needs whole
# lane blocks), a window of 12 under 32 positions and blocks of 16 (tiles
# behind the window exist and are skipped), one dense layer and four expert
# layers, 24 of 40 experts held, 6 a token, renormalised; scaled by 1 and
# not the published 2.826, so that a chosen expert weighs 1 / 6 where the
# cell's weighs 2.826 / 8 (see test_nemotron_cell.py: at toy widths a
# near-tied pick that bfloat16 flips otherwise moves a token's stream)
TOY_CFG = {"hidden_size": 64, "vocab_size": 64, "num_hidden_layers": 5,
           "layer_types": [S, S, S, F, S, S, S, F], "num_dense_layers": 1,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 128, "sliding_window": 12, "intermediate_size": 96,
           "moe_intermediate_size": 48, "num_experts": 24,
           "router_width": 40, "num_experts_per_tok": 6, "route_scale": 1.0}
# as the real limits are set, from two readings of the toy on the CPU over
# three seeds (2**31 + 5, 6, 7; fit(1) then fit(2), like the cell): each
# worst-leaf limit lies between the program's largest (0.042 / 0.023) and the
# float8 control's smallest (0.312 / 0.427); the losses are held at three
# times the program's largest (0.0105; the control reads 0.0016 to 0.038, so
# it is the leaves that fail it). The limits are nearer the program's side
# than the cell's because the faintest planted fault below, positions on the
# ONE full layer of the toy's five, reads 0.082 / 0.086 on seed 2**31 + 5
# (five times that seed's sound 0.015 / 0.019): each of the four planted
# faults comes out not correct
TOY_LIMITS = {"loss_r1_gap": 0.03, "loss_r2_gap": 0.03, "loss_r3_gap": 0.03,
              "grad1_gap": 0.065, "dparam_gap": 0.065}
# the twin at float32, for the fault that computes the router in bfloat16:
# under bfloat16 compute a bfloat16 router reads what the sound program reads
# (0.017 / 0.019 on the worst leaves: its near ties fall either way already).
# The float32 program stays inside these (5.0e-4 / 3.6e-4 on the worst leaves
# when they were set, losses to 1.3e-7); with the router's logits from
# bfloat16 operands it reads 0.012 / 0.005 there and 1.1e-3 to 1.9e-3 on the
# losses
F32_LIMITS = {"loss_r1_gap": 1e-3, "loss_r2_gap": 1e-3, "loss_r3_gap": 1e-3,
              "grad1_gap": 5e-3, "dparam_gap": 5e-3}


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    root, _ = toy.make_root(str(tmp_path_factory.mktemp("trinity")))
    bench = os.path.join(root, "benchmarks")
    path = os.path.join(bench, "configs", "toy_trinity_mini.json")
    cfg = dict(load_json(path), **TOY_CFG)
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "limits", TWIN + ".json"), "w") as f:
        json.dump(TOY_LIMITS, f)
    return Cell(TWIN, root=root)


def _numbers(cell, seed, reference=None):
    sim, prog = window.first_rounds(cell, seed)
    window.release(sim)
    return check.numbers(prog, reference or window.reference_rounds(cell, seed))


def test_the_cell_resolves_at_the_published_widths():
    cell = Cell(CELL, root=toy.REPO)
    bm = load_json(os.path.join(toy.REPO, "BENCHMARK.json"))
    entry = next(c for c in bm["configs"] if c["name"] == "trinity_mini")
    assert entry["reduced"] == cell.cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.cfg["published"] == {"num_hidden_layers": 32,
                                     "num_experts": 128,
                                     "vocab_size": 200192}
    assert (cell.cfg["router_width"], cell.cfg["first_expert_held"]) == (128, 0)
    # every published width
    assert [cell.cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "num_shared_experts", "sliding_window",
        "route_scale", "rms_norm_eps", "rope_theta", "num_dense_layers",
        "global_attn_every_n_layers")] == [
        2048, 32, 4, 128, 6144, 1024, 8, 1, 2048, 2.826, 1e-5, 10000, 2, 4]
    assert len(cell.cfg["layer_types"]) == 32  # kept whole
    assert cell.cfg["deployment"].startswith("8 chips share each expert layer")
    assert "modeling_afmoe.py" in cell.cfg["assumed"]["modeling"]
    ref = load_module("reference", cell.family)
    assert ref.sizes(cell.cfg, cell.job)["kinds"] == [S, S, S, F, S, S, S, F]
    spec = ref.param_spec(cell.cfg, cell.job)
    count = lambda keep: sum(math.prod(s) for k, (s, _) in spec.items()  # noqa: E731
                             if keep(k))
    trains = lambda k: bool({"lora_a", "lora_b", "score"} & set(k.split("/")))  # noqa: E731
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    dense = attention + 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    experts = attention + 2048 * 128 + 128 + 17 * expert
    base = 2 * dense + 6 * experts + 50048 * 2048 + 2048
    assert count(lambda k: not trains(k)) == base and 1.039e9 < base < 1.040e9
    lora = lambda n_in, n_out: 8 * (n_in + n_out)  # noqa: E731
    attn_lora = 2 * lora(2048, 4096) + lora(4096, 2048) + 2 * lora(2048, 512)
    assert count(trains) == (
        8 * attn_lora + 2 * (2 * lora(2048, 6144) + lora(6144, 2048))
        + 6 * (2 * lora(2048, 1024) + lora(1024, 2048)) + 2048 * 4)
    # none in the routed path, none on a norm; every expert's matrices are
    # leaves of their own, never a stack; the selection bias is drawn
    assert not any(("experts_" in k.replace("shared_experts", "")
                    or "/router/" in k or "norm" in k) and trains(k)
                   for k in spec)
    assert spec["layers_2/mlp/experts_15/down_proj/kernel"] == (
        (1024, 2048), "fan_in")
    assert spec["layers_2/mlp/expert_bias"] == ((128,), "embed")
    assert "layers_1/mlp/router/kernel" not in spec  # a dense layer
    job = cell.job
    assert (job["clients"], job["batch"], job["local_steps"],
            job["data"]["seq"], job["data"]["min_len_frac"]) == (4, 1, 2, 8192,
                                                                 0.5)
    assert job["train_examples"] == [4, 6, 8, 10] and job["val_examples"] == 2
    assert job["strategy"] == {"name": "fedavg_adapters",
                               "trainable": ["lora_a", "lora_b", "score"]}
    assert job["optimizer"] == {"name": "sgd", "lr": 0.0005}
    assert (job["rounds_per_fit"], job["check_calls"], job["remat"],
            job["mesh"], job["execution_mode"], job["expect_mode"]) == (
        6, [1, 2], True, None, "pipelined", "pipelined_per_round")
    assert job["attention"] == {"kind": "flash", "block_q": 512,
                                "block_k": 512}
    # the adapter cells' job at four times the length, and nothing else
    other = load_json(os.path.join(BENCH_DIR, "traffic",
                                   "fedavg_lora_seq2048.json"))
    differ = {k for k in job if job[k] != other.get(k)}
    assert differ == {"what", "data"} and job["data"] == dict(
        other["data"], seq=8192)
    assert cell.compute_dtype == "bfloat16" and cell.chips == 1
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"window_flash_ms_per_round", "window_flash_roofline_pct",
                     "gqa_flash_roofline_pct", "moe_experts_ms_per_round",
                     "swiglu_experts_roofline_pct",
                     "attention_ms_per_round", "mlp_ms_per_round",
                     "moe_ms_per_round", "moe_router_ms_per_round",
                     "shared_experts_ms_per_round", "unstaged_device_pct",
                     "unscoped_local_train_pct", "device_idle_pct"}
    assert not names & {"flash_ms_per_round", "flash_roofline_pct",
                        "moe_experts_roofline_pct", "moe_latent_ms_per_round",
                        "routed_experts_roofline_pct",
                        "mla_flash_roofline_pct", "ssm_scan_ms_per_round"}
    # the catalog's numbers, but for the three reduced, at their published
    # values
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert cell.cfg["source"] == entry["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if cell.cfg.get(k) != v} == set(cell.cfg["reduced"])


def test_step_flops_and_kernel_bytes_by_hand():
    cell = Cell(CELL, root=toy.REPO)
    cfg, job = cell.cfg, cell.job
    f = load_module("flops", "afmoe_classifier")
    t, w = 8192, 2048
    band = w * t - w * (w - 1) // 2
    assert f.scores_per_head(t, w) == band == 14681088
    assert f.scores_per_head(t, None) == t * (t + 1) // 2 == 33558528
    assert f.scores_per_head(1024, w) == 1024 * 1025 // 2  # under the window
    proj = 3 * 2 * 2048 * 4096 + 2 * 2 * 2048 * 512
    assert f.attention_flops_per_token(cfg, t, S) == pytest.approx(
        proj + 4 * band * 4096 / t)
    assert f.attention_flops_per_token(cfg, t, F) == pytest.approx(
        proj + 4 * (t + 1) / 2 * 4096)
    assert f.expected_local_assignments(cfg) == 1.0
    expert = 3 * 2 * 2048 * 1024
    assert f.expert_layer_flops_per_token(cfg) == 2 * 2048 * 128 + 2 * expert
    forward = (6 * f.attention_flops_per_token(cfg, t, S)
               + 2 * f.attention_flops_per_token(cfg, t, F)
               + 2 * 3 * 2 * 2048 * 6144 + 6 * (2 * 2048 * 128 + 2 * expert))
    assert f.forward_flops_per_token(cfg, t) == pytest.approx(forward)
    # forward and activation gradients, not 3 x: 17.2 TFLOP a client step
    step = f.train_step_flops(cfg, job)
    assert step == pytest.approx(2 * forward * t)
    assert abs(step / 1e12 - 17.23) < 0.01
    # the attention contractions' share of the required operations
    scores = 6 * band + 2 * t * (t + 1) // 2
    assert abs(4 * scores * 4096 / t / forward - 0.295) < 0.001
    g = load_module("flops", "window_flash")
    assert g.scores_in_window(t, w) == band
    assert g.scores_in_window(1024, w) == 1024 * 1025 / 2
    assert g.call_flops("fwd", 4, t, 32, 128, w) == 2 * 2 * band * 128 * 32 * 4
    assert g.call_flops("dkv", 4, t, 32, 128, w) == 4 * 2 * band * 128 * 32 * 4
    # the bytes are the grouped calls': q and the output at 32 heads, k and
    # v at 4; the mask and lse rows
    assert load_module("flops", "gqa_flash").call_bytes(
        "fwd", 4, t, 32, 4, 128) == 4 * t * (
        (2 * 32 + 2 * 4) * 128 * 2 + 33 * 4)
    # the held experts: 4 x 6,144 real positions a step, 8 picks of 128
    # experts, 16 held: 1,536 rows an expert; compute-bound a pass
    e = load_module("flops", "swiglu_experts")
    assert e.real_positions(job) == 6144 and e.expert_layers(cfg) == 6
    assert e.expected_rows(cfg, 4 * 6144) == 16 * 1536
    assert e.pass_flops(cfg, 4 * 6144) == 16 * 1536 * expert
    assert e.pass_bytes("bwd", cfg, 4 * 6144) == (
        16 * 3 * 2048 * 1024 * 2 + 3 * 16 * 1536 * 2048 * 2)
    one = e.least_seconds("fwd", cfg, 4 * 6144, 197e12, 819e9)
    assert one[1] == "compute" and one[0] == pytest.approx(
        16 * 1536 * expert / 197e12)
    assert e.least_seconds_per_round(
        cfg, job, 197e12, 819e9, ["backward", "forward", "recompute",
                                  "update"]) == pytest.approx(
        3 * one[0] * 6 * 2)
    fwd = g.least_seconds("fwd", 4, t, 32, 4, 128, w, 197e12, 819e9)
    assert fwd[1] == "compute" and fwd[0] == pytest.approx(
        2 * 2 * band * 128 * 32 * 4 / 197e12)
    # a window of one position is bound by its bytes
    assert g.least_seconds("fwd", 4, t, 32, 4, 128, 1, 197e12, 819e9)[1] == (
        "memory")
    calls = {"fwd": 3, "dq": 2, "dkv": 2}
    assert g.least_seconds_of_calls(cfg, job, calls, 197e12, 819e9
                                    ) == pytest.approx(
        2 * band * 128 * 32 * 4 * (3 * 2 + 2 * 3 + 2 * 4) / 197e12)
    # the full layers' calls are counted by the accepted functions, from
    # this configuration's heads
    full = load_module("flops", "gqa_flash").least_seconds_of_calls(
        cfg, job, {"fwd": 1}, 197e12, 819e9)
    assert full == pytest.approx(
        (t + 1) / (2 * t) * 2 * 2 * t * t * 128 * 32 * 4 / 197e12)


def test_toy_twin_is_correct_and_its_float8_control_is_not(twin):
    limits = twin.limits()
    for seed in (2**31 + 5, 2**31 + 6):
        sim, prog = window.first_rounds(twin, seed)
        gauges = {e["event"]: e for e in sim.observability.registry.events
                  }["parameter_split"]
        window.release(sim)
        # runs [S] [SS] [F] [S]: three under the window, one full; T 32, a
        # window of 12, blocks of 16: the diagonal tile and the one before
        assert [gauges[k] for k in (
            "flash_calls_window", "flash_calls_full", "flash_window",
            "flash_window_tiles_live", "flash_window_tiles_causal",
            "moe_experts_held", "moe_router_width", "moe_top_k",
            "flash_calls_lane_indexed", "flash_calls_transposed",
            "remat_saved_names")] == [3, 1, 12, 3, 3, 24, 40, 6, 4, 0, 2]
        ref = window.reference_rounds(twin, seed)
        sound = check.numbers(prog, ref)
        assert check.decide(sound, limits)[0], (seed, sound)
        # frozen leaves compare exactly: 0 on both sides
        frozen = [k for k, v in ref["snapshots"][-1].items() if v == 0.0]
        assert len(frozen) > len(ref["snapshots"][-1]) / 2
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


def _af():
    return importlib.import_module("fl4health_tpu.models.afmoe")


def _window_one_position_long(monkeypatch):
    real = _af().gated_attention
    monkeypatch.setattr(
        _af(), "gated_attention",
        lambda p, u, mask, window, rope, dims: real(
            p, u, mask, None if window is None else window + 1, rope, dims))


def _positions_on_the_full_layers(monkeypatch):
    real = _af().gated_attention
    ds = importlib.import_module("fl4health_tpu.models.deepseek")

    def attention(p, u, mask, window, rope, dims):
        rope = ds.rope_tables(u.shape[1], dims.head_dim,
                              ds.RopeScaling(theta=dims.rope_theta))
        return real(p, u, mask, window, rope, dims)

    monkeypatch.setattr(_af(), "gated_attention", attention)


def _the_gate_dropped(monkeypatch):
    """``o = P v``: the sigmoid's factor left out (a gate of 1)."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(jax.nn, "sigmoid", lambda x: (
        jnp.ones_like(x) if x.shape[-1] == 4 * 128 else
        jax.lax.logistic(x)))


def _a_bfloat16_router(monkeypatch):
    """The router's logits from bfloat16 operands: near-tied picks fall the
    other way and the weights move by some 1e-3."""
    import jax.numpy as jnp

    real = _af().sigmoid_route
    monkeypatch.setattr(_af(), "sigmoid_route", lambda p, u, k, scale: real(
        dict(p, kernel=p["kernel"].astype(jnp.bfloat16).astype(jnp.float32)),
        u.astype(jnp.bfloat16), k, scale))


FAULTS = {
    "a window one position long": _window_one_position_long,
    "positions on the full layers": _positions_on_the_full_layers,
    "the gate dropped": _the_gate_dropped,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_program_is_not_correct(twin, monkeypatch,
                                                       fault):
    """What the limits are for: the program with one thing wrong in the
    window, the positions or the gate comes out not correct, through the
    harness's own comparison."""
    FAULTS[fault](monkeypatch)
    ok, checks = check.decide(_numbers(twin, 2**31 + 5), twin.limits())
    assert not ok, checks


def test_a_bfloat16_router_is_not_correct_where_float32_is_stated(
        twin, monkeypatch):
    """A twin whose configuration states float32, held to float32's limits:
    the program built for it is correct, the same program with the router's
    logits from bfloat16 operands is not (under bfloat16 compute the fault
    drowns in the near ties that compute type flips anyway)."""
    exact = copy.copy(twin)
    exact.cfg = dict(twin.cfg, compute_dtype="float32")
    exact.compute_dtype = "float32"
    seed = 2**31 + 5
    ref = window.reference_rounds(exact, seed)
    sound = _numbers(exact, seed, ref)
    assert check.decide(sound, F32_LIMITS)[0], sound
    _a_bfloat16_router(monkeypatch)
    low = _numbers(exact, seed, ref)
    assert not check.decide(low, F32_LIMITS)[0], low


def test_a_trace_without_the_scopes_reads_none(tmp_path):
    """The parent's program has none of the new scopes: every new reader
    returns None and the result line leaves the metric out."""
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
    names = ("window_flash_ms_per_round", "window_flash_roofline_pct",
             "moe_experts_ms_per_round", "swiglu_experts_roofline_pct")
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None, name
    # and no file at all reads None too
    ctx["cell"] = types.SimpleNamespace(root=os.path.join(root, "nowhere"),
                                        name="old", bench_dir=BENCH_DIR,
                                        cfg=real.cfg, job=real.job)
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None, name
