"""The benchmark's readers of the program's own names in a profiler trace:
``benchmarks/xplane_meta.py`` (the ``tf_op`` name stacks ``ProfileData``
hides), ``layer_metrics/stage_common.py`` (device time by ``fl_stage::``),
``layer_metrics/span_common.py`` (the ``fl::`` spans) and the eight metrics
over them — on synthetic traces built as ``benchmarks/tests/test_trace_reduce``
builds them, on the PR-24 fixture and on the fixture with the spans in it."""

import json
import lzma
import os
import subprocess
import sys
import types

import pytest

from benchmarks import trace_reduce as tr
from benchmarks import xplane_meta
from benchmarks.harness.spec import BENCH_DIR, load_json, load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
SPAN_METRICS = ("prologue_span_ms", "prologue_introspect_ms",
                "producer_host_ms_per_round", "dispatch_ms_per_round",
                "prefetch_wait_ms_per_round", "epilogue_ms_per_round")
STAGE_METRICS = ("local_train_ms_per_round", "server_update_ms_per_round")
MS = 1e6  # ns


def reader(name):
    return load_module("layer_metrics", name)


def unpack(fixture, root, cell="toy"):
    """The fixture where a traced run of ``cell`` leaves its file."""
    folder = os.path.join(root, ".bench_cache", "trace", cell, "plugins",
                          "profile", "fixture")
    os.makedirs(folder)
    path = os.path.join(folder, "host.xplane.pb")
    with lzma.open(os.path.join(FIXTURES, fixture)) as f, open(path, "wb") as out:
        out.write(f.read())
    return path


def ctx_of(trace, root, rounds, cell="toy"):
    return {"trace": trace, "rounds": rounds,
            "cell": types.SimpleNamespace(root=str(root), name=cell,
                                          bench_dir=BENCH_DIR)}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """The PR-24 fixture: a toy flash cell's 3-round call, no ``fl::`` span."""
    root = tmp_path_factory.mktemp("small")
    path = unpack("trace_small.xplane.pb.xz", root)
    return {"root": root, "path": path, "trace": tr.load(path)}


@pytest.fixture(scope="module")
def spans_small(tmp_path_factory):
    """This PR's fixture: a 3-round call of the seq128 cell on a v5e."""
    root = tmp_path_factory.mktemp("spans_small")
    path = unpack("trace_spans_small.xplane.pb.xz", root)
    return {"root": root, "path": path, "trace": tr.load(path)}


# -- xplane_meta ----------------------------------------------------------
def test_xplane_meta_finds_the_name_stacks_of_the_fixture(small):
    stage = reader("stage_common")
    pairs = xplane_meta.tf_ops(small["path"])["/device:TPU:0"]
    assert len(pairs) == 1114
    kinds = [("none" if tf is None else stage.stage_of(tf)) for _, tf in pairs]
    assert (kinds.count("local_train"), kinds.count("server_update"),
            kinds.count(stage.UNATTRIBUTED), kinds.count("none")) == (317, 47, 195, 555)
    by_name = xplane_meta.by_name(pairs)
    assert "jit(fit_round)/vmap(fl_stage::local_train)/reduce_sum:" in by_name.values()
    assert "jit(fit_round)/fl_stage::server_update/reduce_sum:" in by_name.values()
    # the host plane's events carry no name stack
    assert not any(tf for _, tf in xplane_meta.tf_ops(small["path"])["/host:CPU"])


def test_xplane_meta_equals_the_protobuf_library(small):
    """Where TensorFlow's generated ``xplane_pb2`` imports (in a process of
    its own: it brings its own protobuf runtime), both read the same."""
    code = (
        "import json, sys\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n"
        "xs = xplane_pb2.XSpace(); xs.ParseFromString(open(sys.argv[1], 'rb').read())\n"
        "out = {}\n"
        "for p in xs.planes:\n"
        "    names = {k: v.name for k, v in p.stat_metadata.items()}\n"
        "    rows = []\n"
        "    for _, md in sorted(p.event_metadata.items()):\n"
        "        tf = None\n"
        "        for s in md.stats:\n"
        "            if names.get(s.metadata_id) == 'tf_op':\n"
        "                tf = (s.str_value if s.WhichOneof('value') == 'str_value'\n"
        "                      else names.get(s.ref_value))\n"
        "        rows.append([md.name, tf])\n"
        "    out.setdefault(p.name, []).extend(rows)\n"
        "print(json.dumps(out))\n")
    done = subprocess.run([sys.executable, "-c", code, small["path"]],
                          capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        pytest.skip("tensorflow's xplane_pb2 does not import here")
    theirs = json.loads(done.stdout.strip().splitlines()[-1])
    ours = xplane_meta.tf_ops(small["path"])
    assert set(ours) == set(theirs)
    for plane, rows in theirs.items():
        assert sorted(map(tuple, rows), key=repr) == sorted(ours[plane], key=repr)


def test_xplane_meta_refuses_what_is_no_xplane():
    with pytest.raises((ValueError, IndexError)):
        list(xplane_meta.fields(memoryview(b"\x0b\x01\x02")))  # a group tag


# -- stage_common ---------------------------------------------------------
def test_stage_times_conserve_busy_self_time(small):
    stage = reader("stage_common")
    got = stage.by_stage(small["trace"], stage.read_tf_ops(small["path"]))
    ops = small["trace"].op_self_seconds()
    assert set(got) == {"local_train", "server_update", stage.UNATTRIBUTED}
    assert abs(sum(got.values()) - sum(ops.values())) < 1e-9
    assert abs(sum(got.values()) - small["trace"].busy_s()) / small["trace"].busy_s() < 0.02
    # the toy flash cell: the Mosaic calls are under local_train
    assert got["local_train"] > 10 * got["server_update"] > 0


def test_stage_times_on_a_synthetic_lane():
    stage = reader("stage_common")
    lane = tr.DeviceLane([tr.Event("%while", 0, 100), tr.Event("%dot", 10, 60),
                          tr.Event("%copy", 60, 70), tr.Event("%sum", 120, 150),
                          tr.Event("%late", 300, 400)], [], [])
    trace = tr.Trace({0: lane}, {"main#0": [tr.Event(tr.WINDOW_ANNOTATION, 0, 200)]})
    names = {"/device:TPU:0": {
        "%while": "jit(f)/vmap(fl_stage::local_train)/while:",
        "%dot": "jit(f)/vmap(fl_stage::local_train)/fl_stage::dp_clip/dot:",
        "%copy": None, "%sum": "jit(f)/fl_stage::server_update/reduce_sum:"}}
    got = stage.by_stage(trace, names)
    # the while keeps what its body does not cover; the innermost scope wins;
    # an op without a name stack is unattributed; %late is outside the window
    assert {k: round(v * 1e9) for k, v in got.items()} == {
        "local_train": 40, "dp_clip": 50, stage.UNATTRIBUTED: 10,
        "server_update": 30}


@pytest.mark.parametrize("metric", STAGE_METRICS)
def test_stage_metric_reads_the_run_s_file_or_none(small, tmp_path, metric):
    stage = reader("stage_common")
    want = stage.by_stage(small["trace"], stage.read_tf_ops(small["path"]))
    got = reader(metric).read(ctx_of(small["trace"], small["root"], 3))
    key = metric[:-len("_ms_per_round")]
    assert got == pytest.approx(want[key] * 1e3 / 3)
    # no file where a traced run leaves it: nothing to read, and no raise
    assert reader(metric).read(ctx_of(small["trace"], tmp_path, 3)) is None


# -- span_common and the six span metrics -----------------------------------
def ev(name, start_ms, end_ms):
    return tr.Event(name, start_ms * MS, end_ms * MS)


def synthetic_spans():
    """One traced call of 1,000 ms with three rounds. Round 1 misses its
    prefetch (the synchronous build), round 2's fence is nearly all of the
    round, round 3's fence outlasts the round's own end by a clock's jitter,
    and an earlier call's spans lie before the window."""
    main = [
        ev(tr.WINDOW_ANNOTATION, 0, 1000),
        ev("fl::round", -50, -10), ev("fl::device_fence", -40, -20),
        ev("fl::fit_prologue", 1, 301), ev("fl::introspect", 10, 260),
        ev("fl::setup", 270, 280), ev("fl::introspect", -90, -60),
        # round 1: 100 ms, prefetch miss 40 ms, two dispatches, two fences
        ev("fl::round", 310, 410), ev("fl::configure_fit", 310, 355),
        ev("fl::prefetch_wait", 312, 352),
        ev("fl::fit_round", 356, 390), ev("fl::dispatch", 356, 360),
        ev("fl::device_fence", 360, 390),
        ev("fl::eval_round", 392, 408), ev("fl::dispatch", 392, 394),
        ev("fl::device_fence", 394, 408),
        # round 2: 100 ms of which the fences take 96
        ev("fl::round", 420, 520), ev("fl::prefetch_wait", 420.5, 421),
        ev("fl::dispatch", 421, 422), ev("fl::device_fence", 422, 500),
        ev("fl::dispatch", 500, 501), ev("fl::device_fence", 501, 519),
        # round 3: its last fence ends after the round's own end is stamped
        ev("fl::round", 530, 600), ev("fl::prefetch_wait", 530, 530.5),
        ev("fl::dispatch", 531, 533), ev("fl::device_fence", 533, 580),
        ev("fl::dispatch", 581, 582), ev("fl::device_fence", 582, 601),
    ]
    consumer = [ev("fl::epilogue", 411, 417), ev("fl::report", 416, 417),
                ev("fl::epilogue", 521, 530), ev("fl::epilogue", 601, 606)]
    noise = [ev("$threading.py:1 run", 0, 1000)]
    lane = tr.DeviceLane([tr.Event("%op", 356 * MS, 600 * MS)], [], [])
    return tr.Trace({0: lane}, {"main#0": main, "consumer#1": consumer,
                                "other#2": noise})


SYNTHETIC_WANT = {
    "prologue_span_ms": 300.0,
    "prologue_introspect_ms": 250.0,
    # (100 - 30 - 14) + (100 - 78 - 18) + (70 - 47 - 18) = 56 + 4 + 5
    "producer_host_ms_per_round": 65.0 / 3,
    "dispatch_ms_per_round": (4 + 2 + 1 + 1 + 2 + 1) / 3,
    "prefetch_wait_ms_per_round": (40 + 0.5 + 0.5) / 3,
    "epilogue_ms_per_round": (6 + 9 + 5) / 3,
}


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_on_a_synthetic_trace(tmp_path, metric):
    got = reader(metric).read(ctx_of(synthetic_spans(), tmp_path, 3))
    assert got == pytest.approx(SYNTHETIC_WANT[metric])


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_is_none_without_the_programs_spans(small, metric):
    """The parent's program opens no annotation: its trace has none."""
    assert reader(metric).read(ctx_of(small["trace"], small["root"], 3)) is None
    lane = tr.DeviceLane([tr.Event("%op", 0, 10)], [], [])
    bare = tr.Trace({0: lane}, {"main#0": [ev(tr.WINDOW_ANNOTATION, 0, 1)]})
    assert reader(metric).read(ctx_of(bare, small["root"], 3)) is None


def test_span_common_keeps_threads_apart():
    span = reader("span_common")
    trace = synthetic_spans()
    assert list(span.spans(trace, "epilogue")) == ["consumer#1"]
    assert list(span.spans(trace, "round")) == ["main#0"]
    assert len(span.spans(trace, "round")["main#0"]) == 3  # not the earlier call's
    # a fence on another thread takes nothing off the producer's rounds
    trace.host_lines["consumer#1"].append(ev("fl::device_fence", 310, 410))
    assert span.less_ms(trace, "round", "device_fence") == pytest.approx(65.0)


# -- the fixture with the spans in it ---------------------------------------
def test_every_new_metric_reads_the_spans_fixture(spans_small):
    ctx = ctx_of(spans_small["trace"], spans_small["root"], 3)
    got = {m: reader(m).read(ctx) for m in SPAN_METRICS + STAGE_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # read by hand from the trace: rounds of 427 / 415 / 419 ms with 1,168 ms
    # of fences; dispatches of 19, 8, 5 ms (fit) and 12 ms each (eval); round
    # 1 waits 10 ms for its batches, the others do not
    want = {"prologue_span_ms": 2272.78, "prologue_introspect_ms": 2266.21,
            "producer_host_ms_per_round": 31.03, "dispatch_ms_per_round": 22.95,
            "prefetch_wait_ms_per_round": 3.336, "epilogue_ms_per_round": 4.327,
            "local_train_ms_per_round": 360.65, "server_update_ms_per_round": 5.714}
    assert got == pytest.approx(want, rel=1e-3)
    names = {m["name"] for m in load_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]}
    assert set(got) <= names
    older = {m: reader(m).read(dict(ctx, compile_s=1.0, compiles_in_window=0))
             for m in ("fit_prologue_ms", "host_gap_ms_per_round",
                       "dispatches_per_round", "device_idle_pct")}
    # the inside twin of the prologue: the span ends where the first round
    # starts, a little before that round's first launch
    assert 0.9 * older["fit_prologue_ms"] < got["prologue_span_ms"] <= older["fit_prologue_ms"]
    assert got["prologue_introspect_ms"] < got["prologue_span_ms"]
    assert got["dispatch_ms_per_round"] < got["producer_host_ms_per_round"]
    stage = reader("stage_common")
    by = stage.by_stage(spans_small["trace"], stage.read_tf_ops(spans_small["path"]))
    busy = spans_small["trace"].busy_s()
    assert abs(sum(by.values()) - busy) / busy < 0.02
    # 7.5 % of the busy time lies under no stage: the evaluation program
    assert by["local_train"] / busy > 0.9 and 0.05 < by[stage.UNATTRIBUTED] / busy < 0.1


def test_the_spans_fixture_carries_each_span_s_round(spans_small):
    """What the readers do not need and the timeline's reader does: the
    producer's and the consumer's spans of one round share ``round``."""
    from jax.profiler import ProfileData

    rounds = {"fl::round": [], "fl::epilogue": [], "fl::dispatch": []}
    for plane in ProfileData.from_file(spans_small["path"]).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in rounds:
                    rounds[e.name].append(dict(e.stats).get("round"))
    assert rounds["fl::round"] == [1, 2, 3]
    assert rounds["fl::epilogue"] == [1, 2, 3]
    assert rounds["fl::dispatch"] == [1, 1, 2, 2, 3, 3]


# -- the flash kernels' names do not move their classification --------------
@pytest.mark.parametrize("kind, name", [("fwd", "vmap_flash_fwd_"),
                                        ("dq", "transpose_jvp_vmap_flash_dq___"),
                                        ("dkv", "transpose_jvp_vmap_flash_dkv___")])
def test_flash_classify_goes_by_signature_not_by_name(small, kind, name):
    """``name=`` on a ``pallas_call`` renames its HLO instruction (it was
    the enclosing scope's, ``%attn.N``); what ``flash_common`` finds is the
    same call."""
    flash = reader("flash_common")
    lane = small["trace"].devices[0]
    texts = {e.name for e in lane.ops if "tpu_custom_call" in e.name}
    old = next(t for t in texts if (flash.classify(t) or [None])[0] == kind)
    head, rest = old.split(" = ", 1)
    renamed = f"%{name}.1 = {rest}"
    assert head != renamed.split(" = ", 1)[0]
    assert flash.classify(renamed) == flash.classify(old)
    assert tr.short_name(renamed) == name


# -- pass_common, the part and coverage metrics -----------------------------
PART_METRICS = ("attention", "mlp", "norm", "embed", "head", "optimizer",
                "param_cast", "mamba_mixer", "lora", "moe_router",
                "shared_experts")
TRAIN = "jit(fit_round)/vmap(fl_stage::local_train)/while/body/closed_call/"
# (name stack, stage, pass, parts): what both sides' parsers must say
NAME_STACKS = [
    (TRAIN + "jvp(Enc)/layer_0/attn/fl_layer::attention/q_proj/"
     "fl_layer::param_cast/convert_element_type:",
     "local_train", "forward", {"attention", "param_cast"}),
    (TRAIN + "transpose(jvp(Enc))/jvp(Enc)/checkpoint/rematted_computation/"
     "layer_1/fl_layer::norm/ln_mlp/reduce_sum:",
     "local_train", "recompute", {"norm"}),
    (TRAIN + "transpose(jvp(Jamba.forward))/while/body/closed_call/checkpoint/"
     "fl_layer::mamba_mixer/fl_layer::ssm_scan/broadcast_in_dim:",
     "local_train", "backward", {"mamba_mixer", "ssm_scan"}),
    # a fusion of two ops carries both stacks
    (TRAIN + "transpose(jvp(Jamba.forward))/checkpoint/fl_layer::attention/"
     "reshape;checkpoint/fl_layer::attention/fl_layer::lora/mul:",
     "local_train", "backward", {"attention", "lora"}),
    (TRAIN + "fl_layer::optimizer/sub:", "local_train", "update",
     {"optimizer"}),
    ("jit(fit_round)/vmap(fl_stage::local_train)/while:",
     "local_train", "update", set()),
    ("jit(eval_round)/fl_stage::evaluate/vmap(Deepseek.forward)/"
     "fl_layer::mla_attention/fl_layer::mla_flash/jvp(flash_fwd)/pallas_call:",
     "evaluate", None, {"mla_attention", "mla_flash"}),
    ("jit(fit_round)/fl_layer::shared_cast/Jamba.prepare_shared/"
     "convert_element_type:", None, None, {"shared_cast"}),
    ("jit(fit_round)/fl_stage::server_update/transpose(jvp(f))/mul:",
     "server_update", None, set()),
    ("", None, None, set()), (None, None, None, set()),
]


@pytest.mark.parametrize("tf_op,stage,pas,parts", NAME_STACKS)
def test_the_benchmarks_parsers_agree_with_the_programs(tf_op, stage, pas,
                                                        parts):
    """The benchmark may not import the program, so it keeps its own copy
    of ``stage_of`` / ``layers_of`` / ``pass_of``: one table holds both."""
    from fl4health_tpu.observability import stages

    assert (stages.stage_of(tf_op), stages.pass_of(tf_op),
            set(stages.layers_of(tf_op))) == (stage, pas, parts)
    common = reader("stage_common")
    assert common.stage_of(tf_op) == (stage or common.UNATTRIBUTED)
    assert reader("pass_common").pass_of(tf_op) == pas
    assert reader("layer_common").layers_of(tf_op) == parts


def test_the_benchmarks_pass_markers_are_the_programs():
    from fl4health_tpu.observability import stages

    assert reader("pass_common").PASS_MARKERS == stages.PASS_MARKERS
    assert reader("pass_common").PASSES == stages.PASSES


def synthetic_step():
    """One window of 1,000 ns: a scan's ``while`` around a forward product
    in nested parts, a recomputed and a backward one, the update, an op of
    no part, then the evaluation program and an op of no stage."""
    ops = {"%while": (0, 600, "jit(f)/vmap(fl_stage::local_train)/while:"),
           "%fwd": (0, 100, TRAIN + "jvp(m)/fl_layer::mamba_mixer/"
                                    "fl_layer::norm/mul:"),
           "%again": (100, 180, TRAIN + "transpose(jvp(m))/checkpoint/"
                      "rematted_computation/fl_layer::mamba_mixer/dot:"),
           "%bwd": (180, 400, TRAIN + "transpose(jvp(m))/checkpoint/"
                    "fl_layer::mlp/dot:"),
           "%sgd": (400, 450, TRAIN + "fl_layer::optimizer/sub:"),
           "%loss": (450, 500, TRAIN + "jvp(m)/reduce_sum:"),
           "%eval": (700, 850, "jit(e)/fl_stage::evaluate/vmap(m)/"
                     "fl_layer::mlp/dot:"),
           "%cast": (900, 950, "jit(f)/fl_layer::shared_cast/convert:")}
    lane = tr.DeviceLane([tr.Event(k, s, e) for k, (s, e, _) in ops.items()],
                         [], [])
    trace = tr.Trace({0: lane},
                     {"main#0": [tr.Event(tr.WINDOW_ANNOTATION, 0, 1000)]})
    return trace, {"/device:TPU:0": {k: v[2] for k, v in ops.items()}}


def test_passes_and_coverage_on_a_synthetic_lane():
    common = reader("pass_common")
    trace, names = synthetic_step()
    tab = common.table(trace, names)
    ns = lambda d: {k: round(v * 1e9) for k, v in d.items()}  # noqa: E731
    # the while keeps the 100 ns its body does not cover: bookkeeping, update
    assert ns(common.by_pass(tab)) == {"forward": 150, "recompute": 80,
                                       "backward": 220, "update": 150}
    # the four passes conserve the stage's self time
    stage = reader("stage_common").by_stage(trace, names)
    assert sum(common.by_pass(tab).values()) == pytest.approx(
        stage["local_train"])
    assert round(stage["local_train"] * 1e9) == 600
    by = {part: ns(cols) for part, cols in
          common.by_layer_and_pass(trace, names).items()}
    assert by == {
        "mamba_mixer": {"forward": 100, "recompute": 80},
        "norm": {"forward": 100},
        "mlp": {"backward": 220, "evaluate": 150},
        "optimizer": {"update": 50},
        common.UNSCOPED: {"forward": 50, "update": 100},
        # nesting counts %fwd for both its parts and once for coverage
        common.TOTAL: {"forward": 150, "recompute": 80, "backward": 220,
                       "update": 150, "evaluate": 150}}


def _synthetic_ctx(tmp_path, monkeypatch):
    """The synthetic step where a traced run's readers look: its name stacks
    stand in for the trace file's."""
    trace, names = synthetic_step()
    monkeypatch.setattr(reader("stage_common"), "read_tf_ops",
                        lambda path: names)
    monkeypatch.setattr(reader("layer_common"), "read_tf_ops",
                        lambda path: names)
    folder = tmp_path / ".bench_cache" / "trace" / "toy" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(b"")
    return ctx_of(trace, tmp_path, 2)


def test_the_new_metrics_on_a_synthetic_lane(tmp_path, monkeypatch):
    ctx = _synthetic_ctx(tmp_path, monkeypatch)
    got = {m: reader(m).read(ctx) for m in (
        "forward_ms_per_round", "recompute_ms_per_round",
        "backward_ms_per_round", "eval_ms_per_round", "unstaged_device_pct",
        "unscoped_local_train_pct", "mlp_ms_per_round", "norm_ms_per_round",
        "optimizer_ms_per_round", "mamba_mixer_ms_per_round",
        "attention_ms_per_round")}
    ms = 1e-6 / 2  # ns of the window -> ms per round of two
    assert got == pytest.approx({
        "forward_ms_per_round": 150 * ms, "recompute_ms_per_round": 80 * ms,
        "backward_ms_per_round": 220 * ms, "eval_ms_per_round": 150 * ms,
        # %cast of 800 ns busy; the while's own 100 ns and %loss of 600
        "unstaged_device_pct": 100 * 50 / 800,
        "unscoped_local_train_pct": 100 * 150 / 600,
        "mlp_ms_per_round": 370 * ms, "norm_ms_per_round": 100 * ms,
        "optimizer_ms_per_round": 50 * ms,
        "mamba_mixer_ms_per_round": 180 * ms,
        "attention_ms_per_round": None})


@pytest.fixture(scope="module")
def jamba_small(tmp_path_factory):
    """PR 27's fixture: a toy adapter cell's 2-round call on a v5e, remat,
    the scopes that program had (``mamba_mixer``, ``ssm_scan``,
    ``attention``, ``shared_cast``) and none of the later ones."""
    root = tmp_path_factory.mktemp("jamba_small")
    path = unpack("trace_jamba_small.xplane.pb.xz", root)
    return {"root": root, "path": path, "trace": tr.load(path)}


def test_the_new_metrics_read_an_older_programs_trace_or_none(spans_small,
                                                              jamba_small):
    """A parent that lacks the scopes gives ``None`` for what reads them and
    does not raise; the pass and the coverage are read from JAX's own
    markers and the accepted stages, which an older program has too."""
    new = [m["name"] for m in load_json(os.path.join(
        REPO, "BENCHMARK.json"))["per_layer"]]
    # PR 39's, then PR 41's six and PR 43's four over the scopes of
    # families no older trace has
    windowed = new[new.index("window_flash_ms_per_round"):]
    assert windowed == ["window_flash_ms_per_round",
                        "window_flash_roofline_pct",
                        "moe_experts_ms_per_round",
                        "swiglu_experts_roofline_pct"]
    hybrid = new[new.index("ssd_mixer_ms_per_round"):new.index(windowed[0])]
    assert hybrid == ["ssd_mixer_ms_per_round", "ssd_scan_ms_per_round",
                      "ssd_scan_roofline_pct", "gqa_flash_roofline_pct",
                      "moe_latent_ms_per_round", "routed_experts_roofline_pct"]
    new = new[new.index("eval_ms_per_round"):new.index(hybrid[0])]
    assert set(new) == {f"{p}_ms_per_round" for p in PART_METRICS} | {
        "eval_ms_per_round", "unstaged_device_pct", "forward_ms_per_round",
        "backward_ms_per_round", "recompute_ms_per_round",
        "unscoped_local_train_pct"}
    enc = ctx_of(spans_small["trace"], spans_small["root"], 3)
    assert [reader(m).read(enc) for m in hybrid + windowed] == [None] * 10
    got = {m: reader(m).read(enc) for m in new}
    reads = {m for m, v in got.items() if v is not None}
    assert reads == {"forward_ms_per_round", "backward_ms_per_round",
                     "unstaged_device_pct", "unscoped_local_train_pct"}
    # seq128, no remat: forward + backward + update = local_train's 360.65
    assert got["unscoped_local_train_pct"] == pytest.approx(100.0)
    assert got["unstaged_device_pct"] == pytest.approx(7.5, abs=0.3)
    assert 0.45 < got["forward_ms_per_round"] / got["backward_ms_per_round"] < 0.6
    common = reader("pass_common")
    by = common.by_pass(common.of_run(enc))
    assert set(by) == {"forward", "backward", "update"}
    assert sum(by.values()) * 1e3 / 3 == pytest.approx(360.65, rel=1e-4)

    ada = ctx_of(jamba_small["trace"], jamba_small["root"], 2)
    assert [reader(m).read(ada) for m in hybrid + windowed] == [None] * 10
    got = {m: reader(m).read(ada) for m in new}
    assert {m for m, v in got.items() if v is not None} == {
        "forward_ms_per_round", "backward_ms_per_round",
        "recompute_ms_per_round", "unstaged_device_pct",
        "unscoped_local_train_pct", "attention_ms_per_round",
        "mamba_mixer_ms_per_round"}
    stage = reader("stage_common")
    by_stage = stage.by_stage(jamba_small["trace"],
                              stage.read_tf_ops(jamba_small["path"]))
    by = common.by_pass(common.of_run(ada))
    assert sum(by.values()) == pytest.approx(by_stage["local_train"])
    assert got["recompute_ms_per_round"] == pytest.approx(
        by["recompute"] * 1e3 / 2)
    assert got["mamba_mixer_ms_per_round"] > reader(
        "ssm_scan_ms_per_round").read(ada) > 0
