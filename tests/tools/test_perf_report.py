"""tools/perf_report.py: JSONL round log -> per-round summary table."""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO / "tools"))

import perf_report  # noqa: E402


def _log(tmp_path, rounds):
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"ts": 0, "event": "other"}) + "\n")
        for r in rounds:
            f.write(json.dumps({"ts": 0, "event": "round", **r}) + "\n")
    return str(path)


def _round(n, **kw):
    base = dict(round=n, compiles=0, compile_s=0.0, device_wait_s=0.01,
                host_s=0.02, fit_s=0.02, eval_s=0.01,
                broadcast_bytes=1000, gather_bytes=1000,
                participants=4, failures=0)
    base.update(kw)
    return base


def test_load_filters_and_sorts(tmp_path):
    path = _log(tmp_path, [_round(2), _round(1, compiles=12)])
    rounds = perf_report.load_round_events(path)
    assert [r["round"] for r in rounds] == [1, 2]


def test_malformed_lines_skipped(tmp_path):
    path = _log(tmp_path, [_round(1)])
    with open(path, "a") as f:
        f.write("{not json\n")
    assert len(perf_report.load_round_events(path)) == 1


def test_render_table_aligned(tmp_path):
    rounds = [_round(1, compiles=12, broadcast_bytes=4096),
              _round(2)]
    table = perf_report.render_table(rounds)
    lines = table.splitlines()
    assert lines[0].split()[:4] == ["round", "compiles", "compile_ms",
                                   "device_ms"]
    assert len(lines) == 4  # header + rule + 2 rounds
    assert all(len(line) == len(lines[0]) for line in lines)
    assert "4096" in lines[2]


def test_render_missing_fields_dash():
    table = perf_report.render_table([{"round": 1}])
    assert "-" in table.splitlines()[2].split()


def test_summarize_steady_state():
    rounds = [_round(1, compiles=12, compile_s=2.0), _round(2), _round(3)]
    s = perf_report.summarize(rounds)
    assert s["rounds"] == 3
    assert s["total_compiles"] == 12
    assert s["steady_state_recompiles"] == 0
    assert s["broadcast_bytes"] == 3000


def test_cli_table_and_json(tmp_path):
    path = _log(tmp_path, [_round(1, compiles=3), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    )
    assert "compiles" in out.stdout and "steady_state_recompiles" in out.stdout
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    )
    doc = json.loads(out.stdout)
    assert doc["summary"]["total_compiles"] == 3
    assert len(doc["rounds"]) == 2


def test_cli_empty_log_fails(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path)],
        capture_output=True, text=True,
    )
    assert out.returncode == 1


def test_cli_missing_file_exits_2_without_traceback(tmp_path):
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"),
         str(tmp_path / "nope.jsonl")],
        capture_output=True, text=True,
    )
    assert out.returncode == 2
    assert "Traceback" not in out.stderr
    assert "cannot read" in out.stderr


def test_cli_unparseable_log_fails(tmp_path):
    path = tmp_path / "garbage.jsonl"
    path.write_text("{not json\nalso not json\n")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path)],
        capture_output=True, text=True,
    )
    assert out.returncode == 1


def test_telemetry_columns_render_when_present():
    rounds = [_round(1, grad_norm_max=1.25, update_norm_mean=0.5,
                     clip_fraction=0.75, nonfinite=0, divergence_max=0.01),
              _round(2, grad_norm_max=1.5, update_norm_mean=0.4,
                     clip_fraction=float("nan"), nonfinite=2,
                     divergence_max=0.02)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    for col in ("grad_norm", "upd_norm", "clip_frac", "nonfinite", "diverg"):
        assert col in header
    # NaN telemetry (round 2's clip fraction) renders as '-'
    assert "-" in table.splitlines()[3].split()
    assert "0.75" in table.splitlines()[2]


def test_telemetry_columns_absent_for_old_logs():
    table = perf_report.render_table([_round(1), _round(2)])
    header = table.splitlines()[0].split()
    assert "grad_norm" not in header and "diverg" not in header
    # exact legacy shape preserved
    assert header == [h for h, _, _ in perf_report.COLUMNS]


def test_json_mode_passes_telemetry_fields_through(tmp_path):
    path = _log(tmp_path, [_round(1, grad_norm_max=2.0, nonfinite=1)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    )
    doc = json.loads(out.stdout)
    assert doc["rounds"][0]["grad_norm_max"] == 2.0
    assert doc["rounds"][0]["nonfinite"] == 1


# -- ProgramReport ('program' event) rendering -------------------------------

def _program(name, **kw):
    base = dict(name=name, backend="cpu", device_kind="cpu",
                flops=6.6e8, bytes_accessed=1.27e8, peak_hbm_bytes=23688704,
                compile_seconds=1.7, cache_hits=0, cache_misses=1)
    base.update(kw)
    return base


def _log_with_programs(tmp_path, rounds, programs):
    path = _log(tmp_path, rounds)
    with open(path, "a") as f:
        for p in programs:
            f.write(json.dumps({"ts": 0, "event": "program", **p}) + "\n")
    return path


def test_program_events_loaded_last_per_name_sorted(tmp_path):
    path = _log_with_programs(tmp_path, [_round(1)], [
        _program("fit_round", flops=1.0),
        _program("eval_round"),
        _program("fit_round", flops=2.0),  # later report supersedes
    ])
    progs = perf_report.load_program_events(path)
    assert [p["name"] for p in progs] == ["eval_round", "fit_round"]
    assert progs[1]["flops"] == 2.0


def test_program_table_renders_flops_hbm_compile_cache():
    # cache_hit is the derived field carried by the event record
    table = perf_report.render_program_table([
        {**_program("fit_round"), "cache_hit": True},
        {**_program("eval_round"), "flops": None, "peak_hbm_bytes": None,
         "cache_hit": None},
    ])
    lines = table.splitlines()
    assert lines[0].split() == ["program", "flops", "bytes", "hbm_peak",
                                "compile_ms", "cache"]
    assert all(len(line) == len(lines[0]) for line in lines)
    fit_row = next(line for line in lines if "fit_round" in line)
    assert "6.6e+08" in fit_row and "23688704" in fit_row
    assert "1700.0" in fit_row and "hit" in fit_row
    eval_row = next(line for line in lines if "eval_round" in line)
    assert "-" in eval_row.split()  # None flops/hbm/cache render as '-'


def test_cli_renders_program_table_when_present(tmp_path):
    path = _log_with_programs(
        tmp_path, [_round(1)],
        [{**_program("fit_chunk_eval"), "cache_hit": False}],
    )
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    )
    assert "fit_chunk_eval" in out.stdout and "hbm_peak" in out.stdout
    out_json = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    )
    doc = json.loads(out_json.stdout)
    assert doc["programs"][0]["name"] == "fit_chunk_eval"


def test_cli_output_byte_stable_without_program_events(tmp_path):
    """Legacy logs (no introspection) must render the exact pre-PR shape:
    no program table, no 'programs' JSON key."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    )
    assert "hbm_peak" not in out.stdout and "program" not in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert "programs" not in doc


# -- resilience fault / quarantine tables (resilience subsystem PR) --------

def _log_with_events(tmp_path, rounds, extra):
    path = _log(tmp_path, rounds)
    with open(path, "a") as f:
        for rec in extra:
            f.write(json.dumps({"ts": 0, **rec}) + "\n")
    return path


def test_fault_table_renders_drops_and_kinds():
    faults = [
        {"round": 1, "dropped": [6], "corrupted": [1, 2],
         "kinds": {"sign_flip": [1], "nan": [2]}},
        {"round": 2, "dropped": [], "corrupted": [1],
         "kinds": {"sign_flip": [1]}},
    ]
    table = perf_report.render_fault_table(faults)
    lines = table.splitlines()
    assert lines[0].split() == ["round", "dropped", "corrupted", "kinds"]
    assert "1,2" in lines[2] and "nan,sign_flip" in lines[2]
    assert lines[3].split()[1] == "-"  # no drops in round 2


def test_quarantine_table_renders_transitions():
    events = [
        {"round": 3, "source": "strategy", "active": [2, 5],
         "entered": [5], "released": []},
        {"round": 7, "source": "watchdog", "active": [2],
         "entered": [], "released": [5]},
    ]
    table = perf_report.render_quarantine_table(events)
    lines = table.splitlines()
    assert lines[0].split() == ["round", "source", "active", "entered",
                                "released"]
    assert lines[2].split() == ["3", "strategy", "2", "5", "-"]
    assert lines[3].split() == ["7", "watchdog", "1", "-", "5"]


def test_cli_renders_fault_and_quarantine_tables(tmp_path):
    path = _log_with_events(
        tmp_path, [_round(1)],
        [{"event": "fault", "round": 1, "dropped": [0], "corrupted": [3],
          "kinds": {"nan": [3]}},
         {"event": "quarantine", "round": 1, "source": "strategy",
          "active": [3], "entered": [3], "released": []}],
    )
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path)],
        capture_output=True, text=True, check=True,
    )
    assert "dropped" in out.stdout and "entered" in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert doc["faults"][0]["corrupted"] == [3]
    assert doc["quarantine"][0]["active"] == [3]


def test_cli_output_byte_stable_without_resilience_events(tmp_path):
    """Legacy logs (no fault plan, no quarantine) render the exact pre-PR
    shape: no fault/quarantine tables, no new JSON keys."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    )
    assert "dropped" not in out.stdout and "quarantine" not in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert "faults" not in doc and "quarantine" not in doc


def test_recovery_table_renders_attempts():
    events = [
        {"round": 5, "phase": "engage", "attempt": 1, "rung": "retry",
         "kind": "training_health", "suspects": [1, 2],
         "resume_round": 3},
        {"round": 5, "phase": "engage", "attempt": 2, "rung": "quarantine",
         "kind": "training_health", "suspects": [1, 2],
         "resume_round": 4},
        {"round": 8, "phase": "probation_passed", "healthy_rounds": 3},
    ]
    table = perf_report.render_recovery_table(events)
    lines = table.splitlines()
    assert lines[0].split() == ["round", "phase", "attempt", "rung",
                                "kind", "suspects", "resume"]
    assert lines[2].split() == ["5", "engage", "1", "retry",
                                "training_health", "1,2", "3"]
    assert lines[3].split()[3] == "quarantine"
    assert lines[4].split()[1] == "probation_passed"


def test_cli_renders_recovery_table_and_json_keys(tmp_path):
    path = _log_with_events(
        tmp_path, [_round(1)],
        [{"event": "recovery", "round": 1, "phase": "engage",
          "attempt": 1, "rung": "quarantine", "kind": "client_failures",
          "suspects": [2], "resume_round": 1}],
    )
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path)],
        capture_output=True, text=True, check=True,
    )
    assert "rung" in out.stdout and "quarantine" in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert doc["recovery"][0]["suspects"] == [2]
    assert doc["recovery"][0]["rung"] == "quarantine"


def test_cli_output_byte_stable_without_recovery_events(tmp_path):
    """Legacy logs (no recovery supervisor) render the exact pre-PR shape:
    no recovery table, no 'recovery' JSON key."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    )
    assert "rung" not in out.stdout and "recovery" not in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert "recovery" not in doc


def test_wire_columns_render_when_fields_present(tmp_path):
    rounds = [_round(1, gather_bytes_wire=512,
                     wire_compression_ratio=13.1),
              _round(2, gather_bytes_wire=512,
                     wire_compression_ratio=13.0)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "wire_bytes" in header and "wire_ratio" in header
    assert "13.1x" in table and "512" in table
    summary = perf_report.summarize(rounds)
    assert summary["gather_bytes_wire"] == 1024


def test_wire_fields_absent_keeps_legacy_table_byte_stable(tmp_path):
    """Logs from uncompressed runs must render the EXACT pre-compression
    output — header set, alignment and summary keys unchanged."""
    rounds = [_round(1), _round(2)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "wire_bytes" not in header and "wire_ratio" not in header
    assert header == [h for h, _, _ in perf_report.COLUMNS]
    assert "gather_bytes_wire" not in perf_report.summarize(rounds)


def test_cli_output_byte_stable_without_wire_fields(tmp_path):
    """End-to-end CLI: a legacy log renders identically whether or not the
    wire columns exist in the tool (snapshot vs a hand-stripped module is
    overkill — pin the absence of the new markers instead)."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "wire" not in out
    assert "gather_bytes_wire" not in out


def test_cli_json_includes_wire_fields_when_present(tmp_path):
    path = _log(tmp_path, [_round(1, gather_bytes_wire=256,
                                  wire_compression_ratio=8.5)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout
    doc = json.loads(out)
    assert doc["summary"]["gather_bytes_wire"] == 256
    assert doc["rounds"][0]["wire_compression_ratio"] == 8.5


# -- mesh / per-chip columns (mesh-sharded round programs PR) ---------------

def test_mesh_columns_render_when_fields_present():
    rounds = [_round(1, mesh_devices=8, mesh_client_axis=8,
                     steps_per_s_per_chip=12.5, tflops_per_chip=0.031),
              _round(2, mesh_devices=8, mesh_client_axis=8,
                     steps_per_s_per_chip=13.5, tflops_per_chip=0.033)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "chips" in header and "steps/s/chip" in header
    assert "tflops/chip" in header
    assert "12.5" in table
    summary = perf_report.summarize(rounds)
    assert summary["mesh_devices"] == 8
    assert summary["steps_per_s_per_chip"] == 13.0


def test_mesh_fields_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "chips" not in header and "steps/s/chip" not in header
    assert header == [h for h, _, _ in perf_report.COLUMNS]
    summary = perf_report.summarize(rounds)
    assert "mesh_devices" not in summary
    assert "steps_per_s_per_chip" not in summary


def test_program_table_mesh_column_only_when_present():
    programs = [
        {"name": "fit_round", "flops": 1e9, "bytes_accessed": 1e6,
         "peak_hbm_bytes": 1024, "compile_seconds": 0.5, "cache_hit": True},
    ]
    table = perf_report.render_program_table(programs)
    assert "mesh" not in table.splitlines()[0]
    programs_mesh = [
        {**programs[0],
         "mesh": {"axes": {"clients": 8}, "n_devices": 8}},
        {"name": "eval_round", "flops": 1e8},
    ]
    table = perf_report.render_program_table(programs_mesh)
    header = table.splitlines()[0].split()
    assert header[-1] == "mesh"
    assert "clients=8" in table
    # a mesh-less record in a mesh table renders '-'
    assert table.splitlines()[-1].split()[-1] == "-"


def test_cli_output_has_no_mesh_markers_for_legacy_log(tmp_path):
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "chips" not in out
    assert "steps/s/chip" not in out
    assert "mesh" not in out


def test_precision_columns_render_when_fields_present():
    rounds = [_round(1, compute_dtype="bfloat16"),
              _round(2, compute_dtype="bfloat16")]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "dtype" in header
    assert "bfloat16" in table
    summary = perf_report.summarize(rounds)
    assert summary["compute_dtype"] == "bfloat16"


def test_loss_scale_skips_column_and_cumulative_summary():
    rounds = [_round(1, compute_dtype="float16", loss_scale_skips=1.0),
              _round(2, compute_dtype="float16", loss_scale_skips=3.0)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "ls_skips" in header
    assert table.splitlines()[2].split()[-1] == "1"
    # cumulative counter: the run total is the max, not the sum
    assert perf_report.summarize(rounds)["loss_scale_skips"] == 3


def test_precision_fields_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "dtype" not in header and "ls_skips" not in header
    assert header == [h for h, _, _ in perf_report.COLUMNS]
    summary = perf_report.summarize(rounds)
    assert "compute_dtype" not in summary
    assert "loss_scale_skips" not in summary


def test_cli_output_has_no_precision_markers_for_legacy_log(tmp_path):
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "dtype" not in out
    assert "ls_skips" not in out


def test_program_table_unaffected_by_precision_descriptor():
    """A ``precision`` key on program events must not disturb the program
    table (it is a manifest-style descriptor, not a column)."""
    programs = [
        {"name": "fit_round", "flops": 1e9, "bytes_accessed": 1e6,
         "peak_hbm_bytes": 1024, "compile_seconds": 0.5, "cache_hit": True,
         "precision": {"compute_dtype": "bfloat16", "loss_scale": "none"}},
    ]
    table = perf_report.render_program_table(programs)
    assert "fit_round" in table
    assert "bfloat16" not in table


def test_async_columns_render_when_fields_present():
    rounds = [
        _round(1, async_buffer=4, staleness_mean=0.5, staleness_max=2.0,
               async_cadence_vs=0.67, async_virtual_time_s=0.67),
        _round(2, async_buffer=4, staleness_mean=0.0, staleness_max=0.0,
               async_cadence_vs=0.71, async_virtual_time_s=1.38),
    ]
    table = perf_report.render_table(rounds)
    head = table.splitlines()[0]
    assert "buffer" in head and "stale_avg" in head
    assert "stale_max" in head and "cadence_vs" in head
    assert "0.50" in table and "0.67" in table


def test_async_summary_keys():
    rounds = [
        _round(1, async_buffer=2, staleness_mean=0.5, staleness_max=3.0,
               async_cadence_vs=0.6),
        _round(2, async_buffer=2, staleness_mean=0.0, staleness_max=1.0,
               async_cadence_vs=0.8),
    ]
    s = perf_report.summarize(rounds)
    assert s["async_cadence_vs"] == 0.7
    assert s["staleness_max"] == 3


def test_async_fields_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    with_async = rounds + [
        _round(3, async_buffer=2, staleness_mean=0.1, staleness_max=1.0,
               async_cadence_vs=0.9),
    ]
    legacy = perf_report.render_table(rounds)
    assert "buffer" not in legacy.splitlines()[0]
    assert "cadence_vs" not in legacy.splitlines()[0]
    # summary too: no async keys sneak into sync logs
    s = perf_report.summarize(rounds)
    assert "async_cadence_vs" not in s and "staleness_max" not in s
    # ...and a mixed log renders the columns
    assert "cadence_vs" in perf_report.render_table(with_async)


def test_cli_output_byte_stable_without_async_fields(tmp_path):
    """End-to-end: a legacy (sync) log's CLI output must not change at
    all because async columns exist in the tool."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "buffer" not in out
    assert "stale" not in out
    assert "cadence" not in out
    assert "async" not in out


# -- scenario-sweep leaderboard (fl4health_tpu/sweep/ PR) -------------------

def _sweep_cell(i, **kw):
    base = dict(cell=i, label=f"fedavg/sgd/p0/c3/s{i}",
                strategy="fedavg", client="sgd", partitioner="p0",
                cohort=3, bucket=3, fault="none", seed=i, scalars={},
                final_fit_loss=1.0 - 0.1 * i, final_eval_loss=0.9 - 0.1 * i,
                best_eval_loss=0.9 - 0.1 * i, rounds_to_target=None,
                steps_per_s=12.0, wall_s=0.5, compiles_attributed=0.5)
    base.update(kw)
    return {"event": "sweep", **base}


def _sweep_summary(**kw):
    base = dict(cells=2, groups=1, buckets=[3], programs_compiled=1,
                compile_s_total=0.8, cells_per_compile=2.0, wall_s=1.2)
    base.update(kw)
    return {"event": "sweep_summary", **base}


def test_sweep_leaderboard_ranks_best_first_nans_last():
    cells = [_sweep_cell(1), _sweep_cell(2),
             _sweep_cell(3, final_eval_loss=float("nan"),
                         best_eval_loss=float("nan"))]
    table = perf_report.render_sweep_leaderboard(cells)
    lines = table.splitlines()
    assert lines[0].split() == ["cell", "config", "final_loss", "best_loss",
                                "to_target", "steps/s", "compiles"]
    # cell 2 (0.7) beats cell 1 (0.8); the NaN cell ranks last with '-'
    body = [ln.split() for ln in lines[2:]]
    assert [r[0] for r in body] == ["2", "1", "3"]
    assert body[-1][2] == "-"


def test_cli_sweep_flag_renders_leaderboard_only(tmp_path):
    path = _log_with_events(
        tmp_path, [_round(1)],
        [_sweep_cell(1), _sweep_cell(2), _sweep_summary()],
    )
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--sweep"],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "final_loss" in out and "cells_per_compile: 2.0" in out
    assert "compile_ms" not in out  # no round table in --sweep mode
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--sweep", "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert len(doc["sweep"]) == 2
    assert doc["sweep_summary"]["programs_compiled"] == 1


def test_cli_sweep_only_log_renders_without_round_events(tmp_path):
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for rec in (_sweep_cell(1), _sweep_summary(cells=1)):
            f.write(json.dumps({"ts": 0, **rec}) + "\n")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path)],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "final_loss" in out and "programs_compiled: 1" in out


def test_cli_sweep_flag_fails_loudly_without_sweep_events(tmp_path):
    path = _log(tmp_path, [_round(1)])
    res = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--sweep"],
        capture_output=True, text=True,
    )
    assert res.returncode == 1
    assert "no 'sweep' events" in res.stderr


def test_cli_output_byte_stable_without_sweep_events(tmp_path):
    """Legacy logs must render the exact pre-sweep shape: no leaderboard,
    no sweep JSON keys."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "final_loss" not in out and "sweep" not in out
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert "sweep" not in doc and "sweep_summary" not in doc


def test_sweep_leaderboard_tolerates_null_and_nan_loss_mix():
    cells = [_sweep_cell(1), _sweep_cell(2, final_eval_loss=None),
             _sweep_cell(3, final_eval_loss=float("nan"))]
    lines = perf_report.render_sweep_leaderboard(cells).splitlines()
    assert lines[2].split()[0] == "1"  # the real loss ranks first
    assert {r.split()[2] for r in lines[3:]} == {"-"}


def test_cli_sweep_only_log_honors_json(tmp_path):
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for rec in (_sweep_cell(1), _sweep_summary(cells=1)):
            f.write(json.dumps({"ts": 0, **rec}) + "\n")
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(path),
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert len(doc["sweep"]) == 1
    assert doc["sweep_summary"]["cells"] == 1


# -- durable-checkpoint columns (preemption-survivable federation PR) -------

def test_ckpt_columns_render_when_checkpoint_events_present(tmp_path):
    path = _log_with_events(
        tmp_path, [_round(1), _round(2)],
        [{"event": "checkpoint", "round": 2, "generation": 1,
          "bytes": 4096, "write_ms": 3.25, "kind": "sync"}],
    )
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    header = out.splitlines()[0].split()
    assert "ckpt_ms" in header and "ckpt_bytes" in header
    assert "4096" in out and "3.2" in out
    # round 1 had no save (off-cadence): renders '-' in the ckpt columns
    row1 = out.splitlines()[2].split()
    assert row1[header.index("ckpt_ms")] == "-"
    assert "ckpt_writes: 1" in out
    assert "ckpt_bytes: 4096" in out


def test_ckpt_fields_merge_sums_multiple_frames_per_round():
    rounds = perf_report.merge_checkpoint_fields(
        [_round(1)],
        [{"round": 1, "bytes": 100, "write_ms": 1.0},
         {"round": 1, "bytes": 50, "write_ms": 0.5}],
    )
    assert rounds[0]["ckpt_bytes"] == 150
    assert rounds[0]["ckpt_write_ms"] == 1.5
    summary = perf_report.summarize(rounds)
    assert summary["ckpt_writes"] == 1
    assert summary["ckpt_bytes"] == 150


def test_ckpt_fields_absent_keeps_legacy_table_byte_stable(tmp_path):
    """Logs without `checkpoint` events must render the EXACT legacy
    output — header set and summary keys unchanged."""
    rounds = perf_report.merge_checkpoint_fields(
        [_round(1), _round(2)], []
    )
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    assert "ckpt_ms" not in header and "ckpt_bytes" not in header
    assert header == [h for h, _, _ in perf_report.COLUMNS]
    assert "ckpt_writes" not in perf_report.summarize(rounds)
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "ckpt" not in out


def test_cli_json_includes_checkpoint_events_when_present(tmp_path):
    path = _log_with_events(
        tmp_path, [_round(1)],
        [{"event": "checkpoint", "round": 1, "generation": 2,
          "bytes": 2048, "write_ms": 1.5, "kind": "async"}],
    )
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert doc["checkpoints"][0]["generation"] == 2
    assert doc["rounds"][0]["ckpt_bytes"] == 2048
    assert doc["summary"]["ckpt_write_ms"] == 1.5


def test_cohort_columns_render_when_fields_present():
    rounds = [
        _round(1, cohort_slots=64, cohort_valid=60, registry_size=100000,
               registry_dirty_rows=60, stage_ms=12.5, gather_ms=3.0,
               scatter_ms=1.25, staged_bytes=1 << 20),
        _round(2, cohort_slots=64, cohort_valid=64, registry_size=100000,
               registry_dirty_rows=118, stage_ms=11.0, gather_ms=2.8,
               scatter_ms=1.0, staged_bytes=1 << 20),
    ]
    table = perf_report.render_table(rounds)
    head = table.splitlines()[0]
    assert "slots" in head and "cohort" in head and "registry" in head
    assert "stage_ms" in head and "scatter_ms" in head
    assert "100000" in table and "12.5" in table


def test_cohort_summary_keys():
    rounds = [
        _round(1, cohort_slots=8, cohort_valid=8, registry_size=500,
               stage_ms=10.0, scatter_ms=2.0),
        _round(2, cohort_slots=8, cohort_valid=7, registry_size=500,
               stage_ms=14.0, scatter_ms=4.0),
    ]
    s = perf_report.summarize(rounds)
    assert s["cohort_slots"] == 8
    assert s["registry_size"] == 500
    assert s["stage_ms_mean"] == 12.0
    assert s["scatter_ms_mean"] == 3.0


def test_cohort_fields_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    with_cohort = rounds + [
        _round(3, cohort_slots=4, cohort_valid=4, registry_size=64,
               stage_ms=1.0, scatter_ms=0.5),
    ]
    legacy = perf_report.render_table(rounds)
    assert "slots" not in legacy.splitlines()[0]
    assert "registry" not in legacy.splitlines()[0]
    s = perf_report.summarize(rounds)
    assert "cohort_slots" not in s and "registry_size" not in s
    assert "registry" in perf_report.render_table(with_cohort)


def test_cli_output_byte_stable_without_cohort_fields(tmp_path):
    """End-to-end: a dense-path log's CLI output must not change at all
    because cohort columns exist in the tool."""
    path = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "slots" not in out and "registry" not in out


def test_chunked_cohort_columns_render_when_fields_present():
    rounds = [
        _round(1, cohort_slots=8, cohort_valid=8, registry_size=500,
               rounds_per_dispatch=8, cohort_draw="in_graph"),
        _round(2, cohort_slots=8, cohort_valid=7, registry_size=500,
               rounds_per_dispatch=8, cohort_draw="in_graph"),
    ]
    table = perf_report.render_table(rounds)
    head = table.splitlines()[0]
    assert "rpd" in head and "draw" in head
    assert "in_graph" in table


def test_chunked_cohort_summary_keys():
    rounds = [
        _round(1, cohort_slots=4, cohort_valid=4, registry_size=64,
               rounds_per_dispatch=1, cohort_draw="host"),
        _round(2, cohort_slots=4, cohort_valid=4, registry_size=64,
               rounds_per_dispatch=32, cohort_draw="in_graph"),
    ]
    s = perf_report.summarize(rounds)
    assert s["rounds_per_dispatch"] == 32
    # mixed draw sites surface as a sorted list; a uniform log collapses
    # to the single string
    assert s["cohort_draw"] == ["host", "in_graph"]
    uniform = perf_report.summarize([rounds[1]])
    assert uniform["cohort_draw"] == "in_graph"


def test_chunk_fields_absent_keeps_pipelined_cohort_table_byte_stable():
    """A PR-13-era pipelined-cohort log (cohort fields but no chunk
    fields) must not grow rpd/draw columns or summary keys."""
    rounds = [
        _round(1, cohort_slots=8, cohort_valid=8, registry_size=500,
               stage_ms=10.0, scatter_ms=2.0),
        _round(2, cohort_slots=8, cohort_valid=7, registry_size=500,
               stage_ms=14.0, scatter_ms=4.0),
    ]
    head = perf_report.render_table(rounds).splitlines()[0]
    assert "rpd" not in head and "draw" not in head
    s = perf_report.summarize(rounds)
    assert "rounds_per_dispatch" not in s and "cohort_draw" not in s


# -- postmortem bundles (--bundle, flight-recorder PR) ----------------------

def _bundle(tmp_path):
    import numpy as np

    from fl4health_tpu.observability.bundle import dump_bundle
    from fl4health_tpu.observability.flightrec import FlightRecorder

    rec = FlightRecorder(window=4)
    for r in (1, 2):
        rec.record_round(
            r, _round(r), fit_loss=0.5 - 0.1 * r, eval_loss=0.6 - 0.1 * r,
            mask=np.ones(4, np.float32),
        )
    return dump_bundle(
        str(tmp_path), {"kind": "training_health", "round": 2,
                        "clients": [1], "message": "halt"},
        recorder=rec,
    )


def test_cli_bundle_renders_ring_with_flight_columns(tmp_path):
    bundle = _bundle(tmp_path)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"),
         "--bundle", bundle],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.splitlines()
    assert lines[0].startswith("postmortem bundle: ")
    assert "verdict: training_health, round 2" in lines[0]
    header = lines[1].split()
    assert "fit_loss" in header and "eval_loss" in header
    assert "round" in header
    assert len([l for l in lines if l and l[0].isspace() or l[:1].isdigit()
                or l.strip().startswith(("1", "2"))]) >= 2


def test_cli_bundle_json_mode(tmp_path):
    bundle = _bundle(tmp_path)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"),
         "--bundle", bundle, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["verdict"]["kind"] == "training_health"
    assert [r["round"] for r in doc["rounds"]] == [1, 2]
    assert doc["rounds"][0]["fit_loss"] == 0.4


def test_cli_bundle_missing_dir_exits_2(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"),
         "--bundle", str(tmp_path / "nope")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "Traceback" not in proc.stderr


def test_cli_without_log_or_bundle_errors(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2


def test_flight_columns_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    header = perf_report.render_table(rounds).splitlines()[0]
    assert "fit_loss" not in header and "eval_loss" not in header


def test_cli_bundle_corrupt_ring_exits_2_without_traceback(tmp_path):
    bundle = _bundle(tmp_path)
    ring = Path(bundle) / "ring.msgpack"
    data = ring.read_bytes()
    i = len(data) // 2
    ring.write_bytes(data[:i] + bytes([data[i] ^ 0xFF]) + data[i + 1:])
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"),
         "--bundle", bundle],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "Traceback" not in proc.stderr
    assert "cannot read bundle" in proc.stderr
    # the full incident-report tool degrades identically
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "postmortem.py"), bundle],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "Traceback" not in proc.stderr


# -- fleet-ledger columns (observability/fleet.py PR) ------------------------

def test_fleet_columns_render_when_present():
    rounds = [_round(1, participants_new=4, participation_gini=0.0,
                     straggler_p99=0.0),
              _round(2, participants_new=0, participation_gini=0.25,
                     straggler_p99=3.0)]
    table = perf_report.render_table(rounds)
    header = table.splitlines()[0].split()
    for col in ("new_clients", "gini", "strag_p99"):
        assert col in header
    assert "0.250" in table.splitlines()[3]
    assert all(len(line) == len(table.splitlines()[0])
               for line in table.splitlines())


def test_fleet_columns_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    header = perf_report.render_table(rounds).splitlines()[0]
    assert "new_clients" not in header and "gini" not in header


def test_fleet_summary_last_value_semantics():
    # gini / straggler_p99 are LIFETIME stats: the summary reports the
    # LAST round's value (current state), while new-client counts sum
    rounds = [_round(1, participants_new=4, participation_gini=0.0,
                     straggler_p99=1.0),
              _round(2, participants_new=2, participation_gini=0.1234567,
                     straggler_p99=2.5)]
    s = perf_report.fleet_summary(rounds)
    assert s == {"fleet_new_clients": 6, "participation_gini": 0.1235,
                 "straggler_p99": 2.5}
    assert perf_report.fleet_summary([_round(1)]) is None


def test_json_mode_carries_fleet_key(tmp_path):
    path = _log(tmp_path, [
        _round(1, participants_new=3, participation_gini=0.0),
        _round(2, participants_new=1, participation_gini=0.2),
    ])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    )
    doc = json.loads(out.stdout)
    assert doc["fleet"]["fleet_new_clients"] == 4
    assert doc["summary"]["fleet_new_clients"] == 4
    # legacy logs carry no fleet key at all
    legacy = _log(tmp_path, [_round(1)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), legacy,
         "--json"],
        capture_output=True, text=True, check=True,
    )
    assert "fleet" not in json.loads(out.stdout)


# ---------------------------------------------------------------------------
# logs written before the cost-model ledger went (PR 29) still hold 'stage'
# events: they are read past, never rendered


def test_cli_reads_past_the_stage_events_of_an_older_log(tmp_path):
    plain = _log(tmp_path, [_round(1), _round(2)])
    older = tmp_path / "older.jsonl"
    with open(plain) as f, open(older, "w") as out:
        out.write(f.read())
        out.write(json.dumps(dict(
            ts=0.0, event="stage", program="fit_round", stage="local_train",
            flops=1e9, bytes_accessed=1e6, fusion_headroom_bytes=1e5)) + "\n")
    for flags in ([], ["--json"]):
        got = [
            subprocess.run(
                [sys.executable, str(REPO / "tools" / "perf_report.py"),
                 str(path), *flags],
                capture_output=True, text=True, check=True,
            ).stdout
            for path in (plain, older)
        ]
        assert got[0] == got[1]


# -- operations-plane columns (SLO engine + admin retune PR) ----------------

def _ops_log(tmp_path, rounds, slo=(), admin=()):
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for r in rounds:
            f.write(json.dumps({"ts": 0, "event": "round", **r}) + "\n")
        for e in slo:
            f.write(json.dumps({"ts": 0, "event": "slo", **e}) + "\n")
        for e in admin:
            f.write(json.dumps({"ts": 0, "event": "admin", **e}) + "\n")
    return str(path)


def test_slo_columns_render_and_forward_fill():
    rounds = [_round(1), _round(2), _round(3)]
    merged = perf_report.merge_slo_fields(
        rounds, [{"round": 2, "slo": "eval_loss", "standing": "breach",
                  "state": "breach", "burn_short": 2.0}])
    table = perf_report.render_table(merged)
    header = table.splitlines()[0].split()
    assert "slo" in header and "burn" in header
    # round 1 predates the first transition: untouched; the standing
    # HOLDS from the transition round onward, burn only at the transition
    assert "slo_state" not in merged[0]
    assert merged[1]["slo_state"] == "breach"
    assert merged[1]["slo_burn"] == 2.0
    assert merged[2]["slo_state"] == "breach"
    assert "slo_burn" not in merged[2]
    assert "2.00" in table


def test_admin_retune_markers_render():
    rounds = [_round(1), _round(2)]
    merged = perf_report.merge_admin_fields(
        rounds, [{"round": 2, "scalars": {"server_lr": 0.02}}])
    table = perf_report.render_table(merged)
    assert "retune" in table.splitlines()[0].split()
    assert "admin_retune" not in merged[0]
    assert merged[1]["admin_retune"] == "server_lr=0.02"
    assert "server_lr=0.02" in table


def test_ops_fields_absent_keeps_legacy_table_byte_stable():
    rounds = [_round(1), _round(2)]
    assert perf_report.merge_slo_fields(rounds, []) is rounds
    assert perf_report.merge_admin_fields(rounds, []) is rounds
    header = perf_report.render_table(rounds).splitlines()[0].split()
    assert "slo" not in header and "burn" not in header
    assert "retune" not in header


def test_cli_ops_log_renders_and_json_gains_keys(tmp_path):
    path = _ops_log(
        tmp_path, [_round(1), _round(2), _round(3)],
        slo=[{"round": 2, "slo": "eval_loss", "standing": "breach",
              "state": "breach", "burn_short": 2.0}],
        admin=[{"round": 3, "scalars": {"server_lr": 0.02}}])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "breach" in out and "server_lr=0.02" in out
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), path,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert doc["slo"][0]["standing"] == "breach"
    assert doc["admin"][0]["scalars"] == {"server_lr": 0.02}


def test_cli_output_byte_stable_without_ops_events(tmp_path):
    legacy = _log(tmp_path, [_round(1), _round(2)])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), legacy],
        capture_output=True, text=True, check=True,
    )
    rounds = perf_report.load_round_events(legacy)
    expected = perf_report.render_table(rounds) + "\n\n" + "\n".join(
        f"{k}: {v}" for k, v in perf_report.summarize(rounds).items()
    ) + "\n"
    assert out.stdout == expected
    assert "slo" not in out.stdout and "retune" not in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), legacy,
         "--json"],
        capture_output=True, text=True, check=True,
    ).stdout)
    assert "slo" not in doc and "admin" not in doc
