"""The cell ``nemotron3_super_120b.fedavg_lora_seq2048``: it resolves from its
files at the published widths, its FLOP and byte functions agree with hand
counts, a toy twin of it (every kind of block, grouped key/value heads through
the flash calls, 24 of 40 experts held, 6 a token) runs ``correct`` on the CPU
while the float8 control does not, planted faults in the router, the expert
body, the grouped addressing, the scan's decay and the compute type come out
not correct, and the six new readers read nothing, without raising, from a
trace that has no such scope (the parent's)."""

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

CELL = "nemotron3_super_120b.fedavg_lora_seq2048"
TWIN = "toy_nemotron3_super_120b.toy_fedavg_lora_seq2048"
# a unit that repeats, then an attention and an expert block, at toy widths:
# 4 query heads of 128 lanes over 2 key/value heads (the grouped addressing
# needs whole lane blocks), 8 state-space heads of 8 in 2 groups, a chunk of
# 8 (four chunks in 32 positions), 24 of 40 experts held, 6 a token,
# renormalised; scaled by 1 and not the published 5, so that a chosen
# expert weighs 1 / 6 as the cell's weighs 5 / 22: at 5 / 6 each the 3.6
# held picks of a token are most of a block's output, a near-tied pick that
# bfloat16 flips moves that token's stream, and the sound toy read up to
# 0.21 / 0.31 on the worst leaves (seed 2**31 + 77)
TOY_CFG = {"hidden_size": 64, "vocab_size": 64, "num_hidden_layers": 6,
           "hybrid_override_pattern": "MEME*E", "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 8,
           "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
           "chunk_size": 8, "n_routed_experts": 24, "router_width": 40,
           "num_experts_per_tok": 6, "routed_scaling_factor": 1.0,
           "moe_latent_size": 32, "moe_intermediate_size": 48,
           "moe_shared_expert_intermediate_size": 96}
# as the real limits are set, from two readings of the toy on the CPU over
# four seeds (2**31 + 5, 6, 7, 77; fit(1) then fit(2), like the cell): each
# worst-leaf limit lies between the program's largest (0.031 / 0.033) and the
# float8 control's smallest (0.305 / 0.334), three times from either; the
# losses are held at three times the program's largest (0.0146; the control
# reads 0.0006 to 0.066, so it is the leaves that fail it). Each of the six
# planted faults below comes out not correct under these limits on seed
# 2**31 + 5
TOY_LIMITS = {"loss_r1_gap": 0.045, "loss_r2_gap": 0.045,
              "loss_r3_gap": 0.045, "grad1_gap": 0.1, "dparam_gap": 0.1}
# the twin at float32, for the fault that computes in bfloat16 all the same:
# the float32 program stays inside these (7.2e-4 / 3.6e-4 on the worst leaves
# when they were set), the bfloat16 one reads 0.015 / 0.021 there
F32_LIMITS = {"loss_r1_gap": 1e-3, "loss_r2_gap": 1e-3, "loss_r3_gap": 1e-3,
              "grad1_gap": 5e-3, "dparam_gap": 5e-3}


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    root, _ = toy.make_root(str(tmp_path_factory.mktemp("nemotron")))
    bench = os.path.join(root, "benchmarks")
    path = os.path.join(bench, "configs", "toy_nemotron3_super_120b.json")
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
    entry = next(c for c in bm["configs"] if c["name"] == "nemotron3_super_120b")
    assert entry["reduced"] == cell.cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.cfg["published"] == {"num_hidden_layers": 88,
                                     "n_routed_experts": 512,
                                     "vocab_size": 131072}
    assert (cell.cfg["router_width"], cell.cfg["first_expert_held"]) == (512, 0)
    # every published width
    assert [cell.cfg[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "chunk_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts_per_tok",
        "routed_scaling_factor", "moe_latent_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "layer_norm_epsilon")] == [
        4096, 128, 64, 8, 128, 4, 128, 32, 2, 128, 22, 5, 1024, 2688, 5376,
        1e-5]
    ref = load_module("reference", cell.family)
    assert ref.sizes(cell.cfg, cell.job)["pattern"] == "MEMEMEM*EME"
    spec = ref.param_spec(cell.cfg, cell.job)
    count = lambda keep: sum(math.prod(s) for k, (s, _) in spec.items()  # noqa: E731
                             if keep(k))
    trains = lambda k: bool({"lora_a", "lora_b", "score"} & set(k.split("/")))  # noqa: E731
    mamba = (4096 * 18560 + 8192 * 4096 + 4 * 10240 + 10240 + 3 * 128 + 8192
             + 4096)  # with its block norm
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    expert = 2 * 1024 * 2688
    experts = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 16 * expert + 4096)
    base = 5 * mamba + attention + 5 * experts + 16384 * 4096 + 4096
    assert count(lambda k: not trains(k)) == base and 1.363e9 < base < 1.365e9
    lora = lambda n_in, n_out: 8 * (n_in + n_out)  # noqa: E731
    assert count(trains) == (
        5 * (lora(4096, 18560) + lora(8192, 4096))
        + 2 * lora(4096, 4096) + 2 * lora(4096, 256)
        + 5 * (lora(4096, 5376) + lora(5376, 4096)) + 4096 * 4)
    # none on the latent projections, which only the routed experts read
    # (the configuration's assumed.adapters has the reason)
    assert not any("latent_proj" in k and trains(k) for k in spec)
    # every expert's matrices are leaves of their own, never a stack; the
    # selection bias is drawn, not zeros
    assert spec["layers_1/mixer/experts_15/down_proj/kernel"] == (
        (2688, 1024), "fan_in")
    assert spec["layers_1/mixer/gate/e_score_correction_bias"] == (
        (512,), "embed")
    assert not any(("experts_" in k or "/gate/" in k) and trains(k)
                   for k in spec)
    assert len([k for k in spec if not trains(k)]) > len(spec) / 2
    job = cell.job
    assert (job["clients"], job["batch"], job["local_steps"],
            job["data"]["seq"], job["data"]["min_len_frac"]) == (4, 1, 2, 2048,
                                                                 0.5)
    assert job["train_examples"] == [4, 6, 8, 10] and job["val_examples"] == 2
    assert job["strategy"] == {"name": "fedavg_adapters",
                               "trainable": ["lora_a", "lora_b", "score"]}
    assert job["optimizer"] == {"name": "sgd", "lr": 0.0005}
    assert (job["rounds_per_fit"], job["check_calls"], job["remat"],
            job["mesh"]) == (6, [1, 2], True, None)
    assert job["attention"] == {"kind": "flash", "block_q": 512,
                                "block_k": 512}
    # the Jamba cell's traffic file to the letter: the two hybrids run one job
    assert cell.workload["traffic"] == next(
        w["traffic"] for w in bm["workloads"]
        if w["name"] == "jamba2_3b.fedavg_lora_seq2048")
    assert load_module("families", cell.family).build_module
    assert cell.compute_dtype == "bfloat16" and cell.chips == 1
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"ssd_mixer_ms_per_round", "ssd_scan_ms_per_round",
                     "ssd_scan_roofline_pct", "gqa_flash_roofline_pct",
                     "moe_latent_ms_per_round", "routed_experts_roofline_pct",
                     "attention_ms_per_round", "moe_ms_per_round",
                     "moe_router_ms_per_round", "shared_experts_ms_per_round",
                     "unstaged_device_pct", "unscoped_local_train_pct",
                     "device_idle_pct"}
    assert not names & {"flash_ms_per_round", "flash_roofline_pct",
                        "moe_experts_roofline_pct", "mlp_ms_per_round",
                        "mla_flash_roofline_pct", "ssm_scan_ms_per_round"}
    # the catalog's numbers, but for the three reduced, at their published
    # values
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"]
                       == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert cell.cfg["source"] == entry["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if cell.cfg.get(k) != v} == set(cell.cfg["reduced"])


def test_step_flops_and_kernel_bytes_by_hand():
    cell = Cell(CELL, root=toy.REPO)
    cfg, job = cell.cfg, cell.job
    f = load_module("flops", "nemotron_h_classifier")
    s = load_module("flops", "ssd_scan")
    # C B^T a group; scores x inputs, the chunk state, C x state a head
    scan = 8 * 2 * 128 * 128 + 128 * (2 * 128 * 64 + 2 * 64 * 128
                                      + 2 * 128 * 64)
    assert s.forward_flops_per_token(cfg) == scan == 6553600
    mamba = 2 * 4096 * 18560 + 2 * 8192 * 4096 + scan
    assert f.mamba_flops_per_token(cfg) == mamba
    attention = 2 * 2 * 4096 * 4096 + 2 * 2 * 4096 * 256 + 2 * 2048 * 4096
    assert f.attention_flops_per_token(cfg, 2048) == attention
    assert f.expected_local_assignments(cfg) == pytest.approx(0.6875)
    routed = 0.6875 * 4 * 1024 * 2688
    block = (2 * 4096 * 512 + 4 * 4096 * 1024 + 4 * 4096 * 5376 + routed)
    assert f.expert_block_flops_per_token(cfg) == pytest.approx(block)
    forward = 5 * mamba + attention + 5 * block
    assert f.forward_flops_per_token(cfg, 2048) == pytest.approx(forward)
    # forward and activation gradients, not 3 x: 7.37 TFLOP a client step
    step = f.train_step_flops(cfg, job)
    assert step == pytest.approx(2 * forward * 2048)
    assert abs(step / 1e12 - 7.372) < 0.001
    assert abs(f.mamba_share(cfg, job) - 0.627) < 0.001
    assert abs(f.routed_share(cfg, job) - 0.0210) < 0.0002
    # the scan's passes: the backward is two matmuls a matmul; the least
    # bytes leave the tiles and the states on the chip
    assert s.pass_flops_per_token("bwd", cfg) == 2 * scan
    fwd_bytes = (8192 + 2048) * 2 + 128 * 4 + 8192 * 4
    assert s.pass_bytes_per_token("fwd", cfg) == fwd_bytes
    assert s.pass_bytes_per_token("bwd", cfg) == fwd_bytes + 10240 * 2 + 512
    secs, bound = s.least_seconds("fwd", 2048, cfg, 197e12, 819e9)
    assert bound == "memory" and secs == pytest.approx(2048 * fwd_bytes / 819e9)
    # a round: 5 Mamba blocks x 4 clients x 2 steps of each pass the trace
    # shows; a recompute costs a forward
    shown = s.least_seconds_per_round(cfg, job, 197e12, 819e9,
                                      ["forward", "backward"])
    assert shown == pytest.approx(40 * 2048 * (
        fwd_bytes + max(s.pass_bytes_per_token("bwd", cfg),
                        2 * scan * 819e9 / 197e12)) / 819e9)
    assert s.least_seconds_per_round(
        cfg, job, 197e12, 819e9, ["forward", "recompute", "backward"]
    ) == pytest.approx(shown + 40 * 2048 * fwd_bytes / 819e9)
    g = load_module("flops", "gqa_flash")
    assert g.call_flops("fwd", 4, 2048, 32, 128) == 2 * 2 * 2048**2 * 128 * 32 * 4
    assert g.call_flops("dkv", 4, 2048, 32, 128) == 4 * 2 * 2048**2 * 128 * 32 * 4
    # q and the output at 32 heads, k and v at 2; the mask and lse rows
    assert g.call_bytes("fwd", 4, 2048, 32, 2, 128) == 4 * 2048 * (
        (2 * 32 + 2 * 2) * 128 * 2 + 33 * 4)
    assert g.call_bytes("dkv", 4, 2048, 32, 2, 128) == 4 * 2048 * (
        (2 * 32 + 4 * 2) * 128 * 2 + 65 * 4)
    fwd = g.least_seconds("fwd", 4, 2048, 32, 2, 128, 197e12, 819e9)
    assert fwd[1] == "compute" and fwd[0] == pytest.approx(
        2049 / 4096 * 2 * 2 * 2048**2 * 128 * 32 * 4 / 197e12)
    # a call covers the four clients: 12 forwards, 8 dQ and 8 dK/dV executed
    calls = {"fwd": 12, "dq": 8, "dkv": 8}
    assert g.least_seconds_of_calls(cfg, job, calls, 197e12, 819e9
                                    ) == pytest.approx(
        2049 / 4096 * 2 * 2048**2 * 128 * 32 * 4 * (12 * 2 + 8 * 3 + 8 * 4)
        / 197e12)
    e = load_module("flops", "routed_latent_experts")
    assert e.expected_rows_per_expert(cfg, 8192) == pytest.approx(352)
    assert e.pass_flops(cfg, 8192) == pytest.approx(16 * 352 * 4 * 1024 * 2688)
    weights = 16 * 2 * 1024 * 2688 * 2
    assert e.pass_bytes("fwd", cfg, 8192) == pytest.approx(
        weights + 2 * 16 * 352 * 1024 * 2)
    assert e.pass_bytes("bwd", cfg, 8192) == pytest.approx(
        weights + 3 * 16 * 352 * 1024 * 2)
    secs, bound = e.least_seconds("fwd", cfg, 8192, 197e12, 819e9)
    assert bound == "compute" or bound == "memory"
    # five expert blocks x two local steps, the clients folded into one call
    # and over the REAL positions, 1,536 of a row's 2,048 in the mean: a pad
    # position picks no expert here
    assert e.real_positions(job) == 1536
    per_round = e.least_seconds_per_round(cfg, job, 197e12, 819e9,
                                          ["forward", "backward"])
    assert per_round == pytest.approx(10 * (
        e.least_seconds("fwd", cfg, 6144, 197e12, 819e9)[0]
        + e.least_seconds("bwd", cfg, 6144, 197e12, 819e9)[0]))


def test_toy_twin_is_correct_and_its_float8_control_is_not(twin):
    limits = twin.limits()
    for seed in (2**31 + 5, 2**31 + 6):
        sim, prog = window.first_rounds(twin, seed)
        gauges = {e["event"]: e for e in sim.observability.registry.events
                  }["parameter_split"]
        window.release(sim)
        assert [gauges[k] for k in (
            "ssd_chunks", "ssd_heads", "moe_experts_held", "moe_router_width",
            "moe_top_k", "flash_calls_lane_indexed", "flash_calls_transposed",
            "remat_saved_names")] == [4, 8, 24, 40, 6, 1, 0, 2]
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


def _nh():
    return importlib.import_module("fl4health_tpu.models.nemotron_h")


def _dropped_selection_bias(monkeypatch):
    import jax.numpy as jnp

    real = _nh().sigmoid_route
    monkeypatch.setattr(_nh(), "sigmoid_route", lambda p, u, k, scale: real(
        dict(p, e_score_correction_bias=jnp.zeros_like(
            p["e_score_correction_bias"])), u, k, scale))


def _weights_not_renormalised(monkeypatch):
    """w_k = 5 s_k: the scale without the division by the chosen's sum."""
    real = _nh().sigmoid_route

    def route(p, u, k, scale):
        import jax

        idx, w = real(p, u, k, scale)
        scores = jax.nn.sigmoid(u @ p["kernel"])
        return idx, scale * jax.numpy.take_along_axis(scores, idx, axis=1)

    monkeypatch.setattr(_nh(), "sigmoid_route", route)


def _relu_for_relu2(monkeypatch):
    import jax

    def relu_expert(x, up, down):
        return jax.nn.relu(x @ up) @ down

    monkeypatch.setattr(_nh(), "relu2_expert", relu_expert)


def _key_head_h_mod_2(monkeypatch):
    """Query head h reads key/value head h % 2, not h // (heads / 2)."""
    import jax.numpy as jnp

    fa = importlib.import_module("fl4health_tpu.kernels.flash_attention")
    real = fa.flash_attention

    def attend(q, k, v, *args, **kw):
        h = q.shape[2]
        order = jnp.asarray(list(range(0, h, 2)) + list(range(1, h, 2)))
        out = real(q[:, :, order], k, v, *args, **kw)
        return out[:, :, jnp.argsort(order)]

    monkeypatch.setattr(fa, "flash_attention", attend)


def _decay_a_position_late(monkeypatch):
    """S_t = exp(dt_{t-1} a) S_{t-1} + dt_t x_t B_t^T: the decay's time step
    from the position before, what the state takes in untouched."""
    import jax.numpy as jnp

    real = _nh().ssd_scan

    def scan(x, dt, a, b, c, chunk):
        late = jnp.concatenate([dt[:, :1], dt[:, :-1]], axis=1)
        scaled = (x.astype(jnp.float32) * (dt / late)[..., None]).astype(
            x.dtype)
        return real(scaled, late, a, b, c, chunk)

    monkeypatch.setattr(_nh(), "ssd_scan", scan)


FAULTS = {
    "a dropped selection bias": _dropped_selection_bias,
    "weights not renormalised": _weights_not_renormalised,
    "relu for relu2": _relu_for_relu2,
    "key head h % 2 for h // 16": _key_head_h_mod_2,
    "a decay applied a position late": _decay_a_position_late,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_program_is_not_correct(twin, monkeypatch,
                                                       fault):
    """What the limits are for: the program with one thing wrong in the
    router, the expert body, the grouped addressing or the scan's decay
    comes out not correct, through the harness's own comparison."""
    FAULTS[fault](monkeypatch)
    ok, checks = check.decide(_numbers(twin, 2**31 + 5), twin.limits())
    assert not ok, checks


def test_bfloat16_where_the_configuration_says_float32_is_not_correct(twin):
    """A twin whose configuration states float32, held to float32's limits:
    the program built for it is correct, the program built in bfloat16 over
    the same configuration's reference is not."""
    exact = copy.copy(twin)
    exact.cfg = dict(twin.cfg, compute_dtype="float32")
    exact.compute_dtype = "float32"
    seed = 2**31 + 5
    ref = window.reference_rounds(exact, seed)
    sound = _numbers(exact, seed, ref)
    assert check.decide(sound, F32_LIMITS)[0], sound
    low = _numbers(twin, seed, ref)  # bfloat16 compute
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
    names = ("ssd_mixer_ms_per_round", "ssd_scan_ms_per_round",
             "ssd_scan_roofline_pct", "gqa_flash_roofline_pct",
             "moe_latent_ms_per_round", "routed_experts_roofline_pct")
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None, name
    # and no file at all reads None too
    ctx["cell"] = types.SimpleNamespace(root=os.path.join(root, "nowhere"),
                                        name="old", bench_dir=BENCH_DIR,
                                        cfg=real.cfg, job=real.job)
    for name in names:
        assert load_module("layer_metrics", name).read(ctx) is None, name


def test_the_executed_passes_and_calls_are_what_a_trace_shows(monkeypatch):
    """The roofline readers count passes and calls from the trace's own
    table, not from the traffic file: a recompute that is not in the trace
    is not in the least time."""
    ex = load_module("layer_metrics", "executed_common")
    pc = load_module("layer_metrics", "pass_common")
    table = {("local_train", "forward", frozenset({"ssd_mixer", "ssd_scan"})): 1.0,
             ("local_train", "backward", frozenset({"ssd_mixer", "ssd_scan"})): 3.0,
             ("local_train", "forward", frozenset({"ssd_mixer"})): 5.0,
             ("evaluate", None, frozenset({"ssd_mixer", "ssd_scan"})): 7.0}
    monkeypatch.setattr(pc, "of_run", lambda ctx: table)
    assert ex.train_seconds_by_pass({}, "ssd_scan") == {"forward": 1.0,
                                                        "backward": 3.0}
    real = Cell(CELL, root=toy.REPO)
    ctx = {"cell": real, "rounds": 2, "dev": types.SimpleNamespace(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12)}
    share = load_module("layer_metrics", "ssd_scan_roofline_pct").read(ctx)
    least = load_module("flops", "ssd_scan").least_seconds_per_round(
        real.cfg, real.job, 197e12, 819e9, ["backward", "forward"])
    assert share == pytest.approx(100 * least * 2 / 4.0)
    table[("local_train", "recompute",
           frozenset({"ssd_mixer", "ssd_scan"}))] = 1.0
    more = load_module("layer_metrics", "ssd_scan_roofline_pct").read(ctx)
    assert more == pytest.approx(100 * 2 * load_module(
        "flops", "ssd_scan").least_seconds_per_round(
        real.cfg, real.job, 197e12, 819e9,
        ["backward", "forward", "recompute"]) / 5.0)


def test_executed_calls_and_passes_are_read_from_a_recorded_trace(tmp_path):
    """The Jamba cell's recorded trace (benchmarks/fixtures, PR 27) has flash
    calls under ``fl_layer::attention`` and scan passes under
    ``fl_layer::ssm_scan``: the shared reader counts the executed calls by
    kernel name under a scope and finds the passes that ran, the recompute
    among them."""
    root = str(tmp_path)
    fixture = os.path.join(toy.REPO, "benchmarks", "fixtures",
                           "trace_jamba_small.xplane.pb.xz")
    folder = os.path.join(root, ".bench_cache", "trace", "rec", "plugins",
                          "profile", "fixture")
    os.makedirs(folder)
    path = os.path.join(folder, "host.xplane.pb")
    with lzma.open(fixture) as f, open(path, "wb") as out:
        out.write(f.read())
    ctx = {"trace": trace_reduce.load(path), "rounds": 2,
           "cell": types.SimpleNamespace(root=root, name="rec",
                                         bench_dir=BENCH_DIR)}
    ex = load_module("layer_metrics", "executed_common")
    # one attention layer, 2 rounds x 2 steps: 4 forwards in training, 8 in
    # the evaluation programs; the kept out / lse spare the recompute's
    assert ex.kernel_calls(ctx, "attention", ["flash_fwd", "flash_dq",
                                              "flash_dkv"]) == {
        "flash_fwd": 12, "flash_dq": 4, "flash_dkv": 4}
    assert ex.kernel_calls(ctx, "ssm_scan", ["flash_fwd"]) == {}
    by_pass = ex.train_seconds_by_pass(ctx, "ssm_scan")
    assert set(by_pass) == {"forward", "recompute", "backward"}
    assert by_pass["backward"] > by_pass["forward"] > 0
    assert ex.train_seconds_by_pass(ctx, "ssd_scan") == {}

