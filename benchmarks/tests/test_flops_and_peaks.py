"""FLOP and byte functions against hand counts; the peaks table."""

import json
import os

import pytest

from benchmarks.harness import device
from benchmarks.harness.spec import BENCH_DIR, load_json, load_module


def test_encoder_step_flops_hand_count():
    cfg = load_json(os.path.join(BENCH_DIR, "configs", "encoder_base.json"))
    f = load_module("flops", "transformer_classifier")
    d, dff, t = 768, 3072, 128
    per_layer = 8 * d * d + 4 * t * d + 4 * d * dff  # 14,548,992
    assert per_layer == 14548992
    job = {"batch": 32, "data": {"seq": t}}
    assert f.train_step_flops(cfg, job) == 3 * 12 * per_layer * t * 32
    # 2.145 TFLOP a step: what PR 23's 42.16 steps/s at 45.9 % MFU implies
    assert abs(f.train_step_flops(cfg, job) / 1e12 - 2.145) < 0.001
    long = {"batch": 8, "data": {"seq": 2048}}
    assert abs(f.attention_share(cfg, long) - 0.3077) < 0.001
    assert abs(f.train_step_flops(cfg, long) / 1e12 - 12.06) < 0.01


def test_flash_call_counts():
    f = load_module("flops", "flash_attention")
    bh, t, d = 96, 2048, 64
    one_dot = 2 * t * t * d * bh
    assert f.call_flops("fwd", bh, t, d) == 2 * one_dot
    assert f.call_flops("dq", bh, t, d) == 3 * one_dot
    assert f.call_flops("dkv", bh, t, d) == 4 * one_dot
    big, row = bh * t * d * 2, bh * t * 4
    assert f.call_bytes("fwd", bh, t, d) == 4 * big + 2 * row
    assert f.call_bytes("dq", bh, t, d) == 5 * big + 3 * row
    assert f.call_bytes("dkv", bh, t, d) == 6 * big + 3 * row
    assert f.padded(2000, 64, 128, 128) == (2048, 64)
    assert f.padded(100, 80, 16, 48) == (144, 128)
    secs, bound = f.least_seconds("fwd", bh, t, d, 197e12, 819e9)
    assert bound == "compute" and abs(secs - 2 * one_dot / 197e12) < 1e-12
    assert f.calls_per_step(True) == {"fwd": 2, "dq": 1, "dkv": 1}


def test_peaks_table_and_unknown_kind():
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    assert "TPU v5e" in table["source"]
    v5e = device.peaks_for(BENCH_DIR, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for(BENCH_DIR, "TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks_for(BENCH_DIR, "cpu")


def test_benchmark_json_names_files_that_exist():
    root = os.path.dirname(BENCH_DIR)
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    e2e = {m["name"] for m in bm["end_to_end"]}
    cells = {w["name"] for w in bm["workloads"]}
    assert "setup_s" in e2e
    for c in bm["configs"]:
        cfg = load_json(os.path.join(root, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in bm["workloads"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        limits = load_json(os.path.join(BENCH_DIR, "limits", w["name"] + ".json"))
        assert {"loss_r1_gap", "grad1_gap"} <= set(limits)
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    assert json.dumps(bm)  # serialisable, under 64 KiB
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024
