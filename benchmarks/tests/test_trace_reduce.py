"""The trace reduction, on synthetic intervals, on the committed 2026-07-31
trace and on a small recorded trace of today's code."""

import lzma
import os

import pytest

from benchmarks import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OLD = os.path.join(REPO, "artifacts", "tpu_trace_20260731_034629")
FIXTURE = os.path.join(REPO, "benchmarks", "fixtures", "trace_small.xplane.pb.xz")


def test_interval_algebra():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert m == [(0, 3), (5, 8)]
    assert tr.total(m) == 6
    assert tr.clip(m, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.gaps(m, 0, 10) == [(3, 5), (8, 10)]
    assert tr.short_name("%convolution_add_fusion.12 = f32[] fusion(...)") == "convolution_add_fusion"
    assert tr.short_name("fusion.3.1") == "fusion"
    assert tr.clean_frame("$simulation.py:2901 _run_round") == "simulation.py:2901:_run_round"


def test_self_times_subtract_enclosed_ops():
    evs = [tr.Event("while", 0, 100), tr.Event("a", 10, 30),
           tr.Event("b", 40, 90), tr.Event("c", 50, 60), tr.Event("d", 120, 130)]
    got = {e.name: ns for e, ns in tr.self_times(evs)}
    assert got == {"while": 30, "a": 20, "b": 40, "c": 10, "d": 10}


def test_idle_gap_goes_to_innermost_program_frame():
    lane = tr.DeviceLane([tr.Event("op", 0, 10), tr.Event("op", 60, 100)], [], [])
    host = {"python#0": [
        tr.Event(tr.WINDOW_ANNOTATION, 0, 100),
        tr.Event("$simulation.py:10 fit", 0, 100),
        tr.Event("$simulation.py:20 _run_round", 5, 70),
        tr.Event("$pxla.py:1 __call__", 20, 50),
    ]}
    t = tr.Trace({0: lane}, host)
    assert t.window == (0, 100) and t.busy_s() == 50e-9
    by = t.idle_by_frame(frozenset({"simulation.py"}), min_gap_ns=1)
    assert by == {"simulation.py:20:_run_round": 50e-9}
    by_any = t.idle_by_frame(frozenset(), min_gap_ns=1)
    assert by_any == {"pxla.py:1:__call__": 50e-9}


def test_collectives_total_and_exposed():
    lane = tr.DeviceLane(
        [tr.Event("%all-reduce.1", 0, 10), tr.Event("%fusion.1", 5, 20)],
        [], [])
    t = tr.Trace({0: lane}, {})
    tot, exposed = t.collectives()
    assert (round(tot * 1e9), round(exposed * 1e9)) == (10, 5)


def test_committed_2026_07_31_trace():
    path = tr.find_xplane(OLD)
    t = tr.load(path)
    assert sorted(t.devices) == [0]
    lane = t.devices[0]
    assert len(lane.launches) == 81 and len(lane.ops) == 1272
    # read by hand from the trace: the device lane spans 106.56 ms and ops
    # cover 19.30 ms of it
    assert abs(t.window_s() - 0.10656) < 1e-4
    assert abs(t.busy_s() - 0.019301) < 1e-5
    ops = t.op_self_seconds()
    assert abs(sum(ops.values()) - t.busy_s()) / t.busy_s() < 0.01
    assert max(ops, key=ops.get) == "fusion"
    idle = t.idle_by_frame(frozenset({"bench.py", "engine.py"}))
    assert abs(sum(idle.values()) - (t.window_s() - t.busy_s())) < 2e-3
    assert t.collectives() == (0.0, 0.0)


def test_small_trace_of_todays_code(tmp_path):
    """Recorded on a v5e at PR 24: the toy twin of the flash cell (2 layers,
    4 clients x 2 steps, 3 rounds in one traced fit() call)."""
    from benchmarks.harness.spec import load_module

    raw = tmp_path / "small.xplane.pb"
    with lzma.open(FIXTURE) as f:
        raw.write_bytes(f.read())
    t = tr.load(str(raw))
    assert len(t.calls) == 1, "the harness's bench_fit_call span is in the trace"
    assert sorted(t.devices) == [0]
    assert 0 < t.busy_s() <= t.window_s()
    assert t.main_module() == "jit_fit_round"
    (start, first), = t.prologues()
    assert 0.4 < (first - start) / 1e9 < 0.6  # read by hand: 504 ms
    assert t.launches() == 104
    ops = t.op_self_seconds()
    assert abs(sum(ops.values()) - t.busy_s()) / t.busy_s() < 0.02
    assert max(ops, key=ops.get) == "attn"  # the Mosaic calls carry the scope
    # the fixture is PR 24's program, which still walked its programs' HLO
    # text in ``observability/hloscan.py`` (gone at PR 29): the frame is in
    # the recording, and the longest idle gap goes to it as the innermost
    # frame of the files named
    idle = t.idle_by_frame(frozenset({"hloscan.py", "simulation.py"}))
    assert max(idle, key=idle.get).startswith("hloscan.py:")
    # 2 layers x 3 rounds x 2 steps x 4 clients vmapped into one call each:
    # 12 dQ, 12 dK/dV, and 12 forward + 12 recomputed + 6 evaluation forwards
    flash = load_module("layer_metrics", "flash_common")
    kinds = [c[0] for c in flash.calls(t)]
    assert (kinds.count("fwd"), kinds.count("dq"), kinds.count("dkv")) == (30, 12, 12)
    assert {c[1:5] for c in flash.calls(t)} == {(32, 256, 64, 2)}


# (fixture, the cell it is a recording of, its rounds, what of the cell's
# configuration and job the recording cut, the metrics the recording is too
# old for). The PR 24 fixture predates the program's ``fl::`` annotations
# (PR 25): the six span readers find nothing there, and return nothing.
SPAN_METRICS = {"prologue_span_ms", "prologue_introspect_ms",
                "producer_host_ms_per_round", "dispatch_ms_per_round",
                "prefetch_wait_ms_per_round", "epilogue_ms_per_round"}
RECORDINGS = [
    ("trace_small", "encoder_base.fedavg_seq2048_flash", 3, {}, {},
     SPAN_METRICS),
    ("trace_spans_small", "encoder_base.fedavg_seq128", 3, {}, {}, set()),
    # test_jamba_cell.py says what this one ran
    ("trace_jamba_small", "jamba2_3b.fedavg_lora_seq2048", 2,
     {"num_hidden_layers": 3, "attn_layer_period": 3, "attn_layer_offset": 1,
      "vocab_size": 4096}, {"seq": 512}, set()),
]


@pytest.mark.parametrize("fixture,name,rounds,cfg_cut,data_cut,too_old",
                         RECORDINGS, ids=[r[0] for r in RECORDINGS])
def test_every_per_layer_reader_reads_the_small_trace(
        tmp_path, fixture, name, rounds, cfg_cut, data_cut, too_old):
    """Each metric BENCHMARK.json lists for a cell (its ``workloads``, or
    none: every cell) through its own reader, on a recorded trace of that
    cell, laid where a run leaves it, with the counters a run hands over."""
    import types

    from benchmarks.harness.device import DeviceInfo
    from benchmarks.harness.spec import BENCH_DIR, Cell, load_module

    folder = (tmp_path / ".bench_cache" / "trace" / name / "plugins"
              / "profile" / "fixture")
    folder.mkdir(parents=True)
    raw = folder / "host.xplane.pb"
    with lzma.open(os.path.join(REPO, "benchmarks", "fixtures",
                                fixture + ".xplane.pb.xz")) as f:
        raw.write_bytes(f.read())
    real = Cell(name, root=REPO)
    cell = types.SimpleNamespace(
        root=str(tmp_path), name=name, bench_dir=BENCH_DIR,
        cfg=dict(real.cfg, **cfg_cut),
        job=dict(real.job, data=dict(real.job["data"], **data_cut)))
    ctx = {"trace": tr.load(str(raw)), "cell": cell,
           "dev": DeviceInfo("tpu", "TPU v5 lite", 1, 1, 197e12, 819e9),
           "rounds": rounds, "call_ms": [602.1], "compile_s": 28.7,
           "compiles_in_window": 0}
    listed = {m["name"] for m in real.metrics("per_layer")}
    assert too_old <= listed
    got = {m: load_module("layer_metrics", m).read(ctx) for m in listed}
    assert {m for m, v in got.items() if v is None} == too_old, got
    assert got["compiles_in_window"] == 0 and got["compile_s"] == 28.7
    assert 0 < got["device_idle_pct"] < 100 and got["fit_prologue_ms"] > 0
    assert got["local_train_ms_per_round"] > got["server_update_ms_per_round"] > 0
    for m in listed - too_old:
        if m.endswith("roofline_pct"):
            assert 0 < got[m] <= 100, (m, got[m])
    if fixture != "trace_small":
        return
    assert abs(got["fit_prologue_ms"] - 504.03) < 0.1
    assert abs(got["dispatches_per_round"] - 104 / 3) < 1e-9
    assert got["compiles_in_window"] == 0 and got["compile_s"] == 28.7
    assert 99 < got["device_idle_pct"] < 100  # toy sizes: the chip mostly waits
    assert 0 < got["host_gap_ms_per_round"] < (602.1 - 504.0) / 3
    # 54 calls of 32 rows x 256 x 64: 2.8 ms of kernels at 11.6 % of roofline
    assert 0.8 < got["flash_ms_per_round"] < 1.1
    assert 11 < got["flash_roofline_pct"] < 12
