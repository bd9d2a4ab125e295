"""The program's spans in the profiler's trace: a toy pipelined ``fit(3)``
under ``jax.profiler.start_trace`` (Python tracer off) must show every span
as an ``fl::<name>`` annotation with its ``round``, on the right thread,
properly nested, on one clock with an annotation the test opens around the
call, and agreeing with the Tracer's own in-memory record."""

import glob
import os
import subprocess
import sys

import jax
import optax
import pytest

from fl4health_tpu.clients import engine
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.observability import Observability
from fl4health_tpu.observability.registry import MetricsRegistry
from fl4health_tpu.observability.spans import ANNOTATION_PREFIX, Tracer
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROUNDS = 3
WINDOW = "test_fit_call"
# the five spans this layer added, with how many a 3-round call opens
# (fit + eval dispatch and fence per round; no test split) and whether each
# carries the round it belongs to
NEW_SPANS = {
    "fit_prologue": (1, False),
    "prefetch_wait": (ROUNDS, True),
    "dispatch": (2 * ROUNDS, True),
    "device_fence": (2 * ROUNDS, False),
    "epilogue": (ROUNDS, True),
}


def _sim(obs):
    datasets = []
    for i in range(3):
        x, y = synthetic_classification(jax.random.PRNGKey(10 + i), 48, (6,), 3)
        datasets.append(ClientDataset(x[:32], y[:32], x[32:], y[32:]))
    return FederatedSimulation(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(12,), n_outputs=3)),
            engine.masked_cross_entropy),
        tx=optax.sgd(0.05), strategy=FedAvg(), datasets=datasets,
        batch_size=8, metrics=MetricManager((efficient.accuracy(),)),
        local_epochs=1, seed=5, observability=obs,
        execution_mode="pipelined",
    )


def _traced_fit(obs, trace_dir):
    """fit(1) to compile, then fit(ROUNDS) under the profiler. Returns
    {thread line index: [(name, start_ns, end_ns, stats)]} of the host
    plane's ``fl::`` events and the test's own window annotation."""
    sim = _sim(obs)
    sim.fit(1)
    obs.tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            sim.fit(ROUNDS)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            found = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith(ANNOTATION_PREFIX) or e.name == WINDOW]
            if found:
                lines[i] = sorted(found, key=lambda e: (e[1], -e[2]))
    return lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    # a private tracer: shutdown leaves its record in place; a private
    # registry: the process-wide one would hand these rounds' events to the
    # next test's metrics.jsonl
    tracer = Tracer()
    lines = _traced_fit(Observability(tracer=tracer, registry=MetricsRegistry()),
                        tmp_path_factory.mktemp("trace"))
    main, = [i for i, evs in lines.items() if any(e[0] == WINDOW for e in evs)]
    return {"lines": lines, "main": main,
            "memory": [e for e in tracer.events if e["ph"] == "X"]}


def _named(events, name):
    return [e for e in events if e[0] == ANNOTATION_PREFIX + name]


@pytest.mark.parametrize("name", sorted(NEW_SPANS))
def test_new_span_is_in_the_trace_with_its_round_on_its_thread(traced, name):
    count, has_round = NEW_SPANS[name]
    by_line = {i: _named(evs, name) for i, evs in traced["lines"].items()
               if _named(evs, name)}
    assert len(by_line) == 1, f"fl::{name} on lines {sorted(by_line)}"
    (line, found), = by_line.items()
    # the consumer's epilogue runs on its own thread, the rest on fit()'s
    assert (line != traced["main"]) == (name == "epilogue")
    assert len(found) == count
    rounds = sorted(e[3]["round"] for e in found if "round" in e[3])
    if has_round:
        per_round = count // ROUNDS
        assert rounds == sorted(list(range(1, ROUNDS + 1)) * per_round)
    else:
        assert rounds == []


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_nest_as_the_code_does(traced):
    main = traced["lines"][traced["main"]]
    prologue, = _named(main, "fit_prologue")
    rounds = _named(main, "round")
    assert [e[3]["round"] for e in rounds] == [1, 2, 3]
    for child in ("introspect", "setup"):
        found, = _named(main, child)
        assert _inside(found, prologue)
    assert prologue[2] <= rounds[0][1], "the prologue ends before round 1"
    for rnd in rounds:
        r = rnd[3]["round"]
        mine = {n: [e for e in _named(main, n) if e[3].get("round") == r]
                for n in ("configure_fit", "prefetch_wait", "fit_round",
                          "eval_round", "dispatch")}
        cfg, = mine["configure_fit"]
        wait, = mine["prefetch_wait"]
        fit, = mine["fit_round"]
        ev, = mine["eval_round"]
        assert _inside(cfg, rnd) and _inside(wait, cfg)
        assert _inside(fit, rnd) and _inside(ev, rnd) and fit[2] <= ev[1]
        d_fit, d_eval = mine["dispatch"]
        assert _inside(d_fit, fit) and _inside(d_eval, ev)
        fences = [e for e in _named(main, "device_fence") if _inside(e, rnd)]
        assert len(fences) == 2
        # enqueue first, then the wait for the device, inside one span
        assert _inside(fences[0], fit) and d_fit[2] <= fences[0][1]
        assert _inside(fences[1], ev) and d_eval[2] <= fences[1][1]
    consumer, = [evs for i, evs in traced["lines"].items()
                 if i != traced["main"]]
    for ep in _named(consumer, "epilogue"):
        for child in ("aggregate", "checkpoint", "report"):
            found = [e for e in _named(consumer, child)
                     if e[3]["round"] == ep[3]["round"]]
            assert found and all(_inside(e, ep) for e in found)


def test_one_clock_with_the_enclosing_annotation(traced):
    window, = [e for e in traced["lines"][traced["main"]] if e[0] == WINDOW]
    events = [e for evs in traced["lines"].values() for e in evs
              if e[0] != WINDOW]
    assert events and all(_inside(e, window) for e in events)
    rounds = _named(traced["lines"][traced["main"]], "round")
    # the producer's round r ends before the consumer's epilogue of r does
    consumer, = [evs for i, evs in traced["lines"].items()
                 if i != traced["main"]]
    for rnd, ep in zip(rounds, _named(consumer, "epilogue")):
        assert rnd[3]["round"] == ep[3]["round"] and ep[2] > rnd[2] - 1e6


def test_trace_agrees_with_the_tracers_own_record(traced):
    in_trace = [e for evs in traced["lines"].values() for e in evs
                if e[0] != WINDOW]
    names = {e["name"] for e in traced["memory"]}
    assert names == {e[0][len(ANNOTATION_PREFIX):] for e in in_trace}
    for name in names:
        mem = sorted(e["dur"] / 1e3 for e in traced["memory"]
                     if e["name"] == name)  # us -> ms
        prof = sorted((e[2] - e[1]) / 1e6 for e in _named(in_trace, name))
        assert len(mem) == len(prof), name
        # the annotation encloses the record, to within a millisecond
        for m, p in zip(mem, prof):
            assert -0.05 <= p - m < 1.0, (name, m, p)


def test_disabled_observability_emits_no_annotation(tmp_path):
    lines = _traced_fit(Observability(enabled=False), tmp_path)
    events = [e for evs in lines.values() for e in evs]
    assert [e[0] for e in events] == [WINDOW]


def test_spans_module_needs_no_jax_and_is_the_only_annotator():
    code = ("import sys; import fl4health_tpu.observability.spans as s; "
            "t = s.Tracer(enabled=False); t.span('x').__enter__(); "
            "s.load_trace; assert 'jax' not in sys.modules, 'jax imported'")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    users = []
    for folder, _, files in os.walk(os.path.join(REPO, "fl4health_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    if "TraceAnnotation" in fh.read():
                        users.append(f)
    assert users == ["spans.py"]
