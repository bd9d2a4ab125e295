"""Smoke tests for the driver entry points (bench.py, __graft_entry__.py).

Round-1 lesson: both entry points drifted out of sync with ``_fit_round``'s
return signature and crashed deterministically; nothing caught it because
neither was executed by any test. These tests execute both on CPU.
"""

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _graft_entry():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module("__graft_entry__")


def test_entry_forward_jits():
    mod = _graft_entry()
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


@pytest.mark.slow
def test_dryrun_multichip_two_devices(eight_devices):
    # conftest provides 8 virtual CPU devices.
    # slow lane: ~20s of whole-stack compile; the MeshConfig machinery it
    # drives is covered in tier-1 by tests/server/test_mesh_fit.py.
    mod = _graft_entry()
    mod.dryrun_multichip(2)


@pytest.mark.slow
def test_dryrun_multichip_eight_devices(eight_devices):
    mod = _graft_entry()
    mod.dryrun_multichip(8)


def test_dryrun_multichip_raises_with_too_few_devices():
    """No move to a virtual mesh: fewer devices than asked for is an error
    that names both counts."""
    mod = _graft_entry()
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"needs {have + 1} devices.*{have}"):
        mod.dryrun_multichip(have + 1)


def _bench_env(**extra):
    env = dict(os.environ)
    env.pop("FL4HEALTH_BENCH_FORCE_CPU", None)
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_bench_refuses_to_measure_without_a_tpu():
    """3a: no probe, no automatic CPU retry — a child that finds no TPU
    fails, and so does the parent, with no record printed."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(FL4HEALTH_BENCH_TIMEOUT_S="300"),
        capture_output=True, text=True, timeout=280,
    )
    assert res.returncode != 0
    assert not [l for l in res.stdout.splitlines() if l.startswith("{")]
    assert "not a TPU" in res.stderr


def test_bench_multichip_raises_on_one_device():
    """3b: no re-exec onto a virtual CPU mesh."""
    env = _bench_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--multichip"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert "needs >= 2 devices, 1 visible" in res.stderr
    assert not res.stdout.strip()


@pytest.mark.slow
def test_bench_produces_json_line():
    env = dict(os.environ)
    env.update(
        FL4HEALTH_BENCH_FORCE_CPU="1",
        FL4HEALTH_BENCH_CLIENTS="4",
        FL4HEALTH_BENCH_BATCH="4",
        FL4HEALTH_BENCH_STEPS="2",
        FL4HEALTH_BENCH_ROUNDS="1",
        FL4HEALTH_BENCH_TIMEOUT_S="540",
    )
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [l for l in res.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1, res.stdout
    record = json.loads(lines[0])
    # core contract keys must be present; provenance/MFU fields ride along
    assert {"metric", "value", "unit", "vs_baseline"} <= set(record)
    assert record["value"] > 0
    assert record["vs_baseline"] > 0
    assert record["platform"] == "cpu"  # FORCE_CPU run must say so
    assert record["metric"].endswith("_cpu_fallback")
    assert record["dtype"] == "float32"
