"""Test configuration: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's smoke-test strategy of spawning N client processes
(/root/reference/tests/smoke_tests/run_smoke_test.py:294-329) — here simulated
clients share one process and are sharded over 8 virtual CPU devices instead.
"""

import os
import sys
from pathlib import Path

# Tier-1 runs on the CPU whatever the machine holds: the device count comes
# from XLA_FLAGS (parsed at backend init, so set before jax is imported) and
# the platform is pinned through jax.config below.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# XLA's CPU AOT cache loader logs a benign machine-feature-mismatch error per
# cached executable (tuning flags like prefer-no-scatter are compared as
# features); silence C++ logging before the backend loads.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:  # `pytest tests/` (no -m) leaves the root off
    sys.path.insert(0, str(_REPO))

from fl4health_tpu.utils.runtime import configure_compile_cache  # noqa: E402

# Persistent compilation cache: XLA compiles dominate suite wall time;
# repeated runs (local iteration, CI re-runs) hit the on-disk cache instead.
# Default <repo>/.jax_test_cache (delete it to force cold compiles), with
# EVERY compile persisted (threshold 0): the fast lane is hundreds of
# sub-second compiles that the default 1 s threshold would re-pay on every
# run. An operator-set JAX_COMPILATION_CACHE_DIR wins — then nothing is
# configured here.
configure_compile_cache(_REPO / ".jax_test_cache", min_compile_time_secs=0.0)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight end-to-end lanes (examples sweep, golden "
        "trajectories, research sweeps). Deselected by default; run with "
        "FL4HEALTH_RUN_SLOW=1 (the CI/driver lane) or -m slow.",
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection lanes (resilience "
        "subsystem). The smoke subset is tier-1-safe and runs by default; "
        "heavier scenarios also carry 'slow'. Select with -m chaos.",
    )
    config.addinivalue_line(
        "markers",
        "multichip: mesh-sharded round-program lanes (parallel/program.py "
        "MeshConfig). Tier-1-safe under this conftest's forced 8-device "
        "virtual CPU platform; select with -m multichip. Tests skip "
        "themselves when fewer than 8 devices are visible "
        "(eight_devices fixture).",
    )
    config.addinivalue_line(
        "markers",
        "sweep: shared-compilation scenario-sweep lanes "
        "(fl4health_tpu/sweep/). The tier-1-safe smoke subset (hoisting "
        "compile-counter pins, small-grid bit-identity parity) runs by "
        "default; exhaustive grids also carry 'slow'. Select with "
        "-m sweep.",
    )
    config.addinivalue_line(
        "markers",
        "crash: crash-drill recovery lanes (resilience/recovery.py — "
        "subprocess fit() SIGKILLed at a seeded point, resumed, pinned "
        "bit-identical). The tier-1-safe smoke subset (in-process "
        "kill-and-resume, ring fallback, one subprocess drill per "
        "execution mode) runs by default; the full kill-matrix "
        "(mid-write, async, corruption variants) also carries 'slow'. "
        "Select with -m crash.",
    )
    config.addinivalue_line(
        "markers",
        "postmortem: flight-recorder / postmortem-bundle lanes "
        "(observability/flightrec.py + bundle.py — bounded black-box "
        "capture, abnormal-end bundles, tools/postmortem.py rendering). "
        "The tier-1-safe smoke subset (bundle round-trips, one SIGTERM "
        "subprocess drill, recorder on/off bit-identity) runs by default; "
        "heavier drill variants also carry 'slow'. Select with "
        "-m postmortem.",
    )
    config.addinivalue_line(
        "markers",
        "selfheal: recovery-supervisor lanes (resilience/supervisor.py — "
        "a RecoveryPolicy escalation ladder turning abnormal ends into "
        "rollback-quarantine-resume). The tier-1-safe smoke subset "
        "(policy/ladder units, suspect attribution, one in-process "
        "self-heal drill per execution mode) runs by default; the full "
        "drill matrix (SIGKILL of the supervised process, cohort "
        "variants) also carries 'slow'. Select with -m selfheal.",
    )
    config.addinivalue_line(
        "markers",
        "bigcohort: cohort-slot registry lanes (server/registry.py "
        "ClientRegistry + CohortConfig). The tier-1-safe smoke subset "
        "(slots=N bit-identity parity, sample_indices/mask coherence, "
        "O(K) compiled-footprint introspection pins) runs by default; "
        "million-client property sweeps and registry-growth benches also "
        "carry 'slow'. Select with -m bigcohort.",
    )
    config.addinivalue_line(
        "markers",
        "fleet: fleet-telescope lanes (observability/fleet.py per-client "
        "lifetime ledger + streaming sketches, /fleet + /clients/<id> "
        "endpoints, cross-silo trace propagation and tools/trace_merge). "
        "The tier-1-safe smoke subset (ledger-on bit-identity per "
        "execution mode, O(participated) memory pins, checkpoint-resume "
        "and rollback survival, live endpoint conformance) runs by "
        "default; registry-scale property sweeps also carry 'slow'. "
        "Select with -m fleet.",
    )
    config.addinivalue_line(
        "markers",
        "roofline: stage-scope lanes (observability/stages.py "
        "named-scope markers, tools/roofline_report.py measured time per "
        "stage from a trace, tools/bench_gate.py). The tier-1-safe subset "
        "(scopes on/off bit-identity per execution mode, every spine "
        "stage's scope found in its compiled round program, gate "
        "pass/regression fixtures) runs by default. "
        "Select with -m roofline.",
    )
    config.addinivalue_line(
        "markers",
        "ops: operations-plane lanes (observability/timeseries.py round "
        "KPI time-series + slo.py burn-rate SLO engine, adminplane.py "
        "live retune endpoint, tools/run_diff.py drift diffing). The "
        "tier-1-safe smoke subset (ops-plane-off bit-identity per "
        "execution mode, the live retune drill at zero recompiles, "
        "endpoint conformance, run_diff exit-code trio) runs by default; "
        "heavier variants also carry 'slow'. Select with -m ops.",
    )


def pytest_collection_modifyitems(config, items):
    """Fast/slow lanes: `pytest tests/` runs the fast lane (<5 min warm);
    FL4HEALTH_RUN_SLOW=1 (or an explicit -m expression) includes the slow
    end-to-end lane. The driver's green-ness command stays `python -m pytest
    tests/ -q`; CI runs both lanes."""
    if os.environ.get("FL4HEALTH_RUN_SLOW") or config.option.markexpr:
        return
    if any("::" in a for a in config.args):
        # The user named specific tests — run exactly what was asked for,
        # slow or not (auto-skipping an explicitly-requested node id would
        # report a green "skipped" to someone trying to debug that test).
        return
    skip_slow = pytest.mark.skip(
        reason="slow lane (set FL4HEALTH_RUN_SLOW=1 or -m slow to run)"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test module. A loaded XLA:CPU
    executable holds memory mappings of its own (one eager gradient through
    the routed layer leaves some 900), JAX keeps every program a process has
    compiled, and Linux gives a process 65,530 mappings
    (``vm.max_map_count``). A tier-1 worker that had run a few of the model
    files stood at that limit, and the next mapping XLA asked for was a
    segmentation fault: reading a cache entry (PR 45's run), writing one,
    compiling (PR 46's runs), always in the same late test of a long file.
    ``tests/models/test_afmoe.py`` alone maps some 45,000: a module is the
    unit that has to stay under the limit."""
    yield
    jax.clear_caches()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture
def tolerance():
    # Reference widens 5e-4 (CPU) to 5e-3 (CUDA); TPU bf16 paths use the wide one.
    # (/root/reference/tests/smoke_tests/conftest.py:5-9)
    return 5e-4
