"""Shared by the pass and coverage metrics: the client step's device time by
pass (forward / recompute / backward / update) and by part x pass, and what
of the device's time lies under no stage or under no part.

The pass is read from JAX's own markers in an op's name stack (the ``tf_op``
stat, ``benchmarks/xplane_meta.py``), the benchmark's copy of the program's
``observability/stages.py pass_of`` (it may not import the program; a test
holds the two to one table): an op under ``fl_stage::local_train`` is
``recompute`` if the stack holds ``rematted_computation`` (what
``jax.checkpoint`` runs again on the way back), else ``backward`` if it holds
``transpose(``, else ``forward`` if it holds ``jvp(``, else ``update``
(optimizer, padding selects, meters). The four sum to the stage's self time.

One sweep over the ops' self times (``trace_reduce.self_times``) keeps, per
(stage, pass, parts), the seconds; every reading here is a sum over that
table. The stage is ``stage_common.stage_of`` (the last ``fl_stage::``), the
parts are ``layer_common.layers_of`` (every ``fl_layer::``: they nest, and an
op counts for each part that holds it but ONCE for coverage). Attribution is
by the name stack XLA left on a fusion, its root's: a norm or a residual add
fused into a matmul counts with the matmul's part.
"""

import functools
import os
from collections import defaultdict

from benchmarks import trace_reduce
from benchmarks.harness.spec import load_module

PASS_MARKERS = (("recompute", "rematted_computation"),
                ("backward", "transpose("),
                ("forward", "jvp("))
PASSES = ("forward", "recompute", "backward", "update")
TRAIN, EVALUATE = "local_train", "evaluate"
UNSCOPED, TOTAL = "_unscoped", "_total"
_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _common(name):
    """``stage_common`` / ``layer_common`` as the readers beside this file
    load them (one module object, so one cache, a file)."""
    return load_module("layer_metrics", name, _HERE)


def pass_of(tf_op):
    """The pass of an op under ``fl_stage::local_train``, else None."""
    if not tf_op or _common("stage_common").stage_of(tf_op) != TRAIN:
        return None
    return next((name for name, marker in PASS_MARKERS if marker in tf_op),
                "update")


def table(trace, tf_ops: dict) -> dict:
    """(stage, pass or None, frozenset of parts) -> self seconds inside the
    window, averaged over the chips of the trace. ``tf_ops``: plane name ->
    {event name: tf_op}."""
    stage_of = _common("stage_common").stage_of
    layers_of = _common("layer_common").layers_of
    lo, hi = trace.window
    acc = defaultdict(float)
    for chip, lane in trace.devices.items():
        names = tf_ops.get(f"/device:TPU:{chip}", {})
        keys = {}
        inside = [e for e in lane.ops if e.end > lo and e.start < hi]
        for e, ns in trace_reduce.self_times(inside):
            key = keys.get(e.name)
            if key is None:
                tf_op = names.get(e.name)
                key = keys[e.name] = (stage_of(tf_op), pass_of(tf_op),
                                      frozenset(layers_of(tf_op)))
            acc[key] += ns
    n = max(len(trace.devices), 1)
    return {k: v / 1e9 / n for k, v in acc.items()}


def by_pass(tab: dict) -> dict:
    """pass -> seconds of the ops under ``local_train``."""
    out = defaultdict(float)
    for (stage, pas, _), s in tab.items():
        if stage == TRAIN:
            out[pas] += s
    return dict(out)


def by_layer_and_pass(trace, tf_ops: dict) -> dict:
    """part -> {pass -> seconds under ``local_train``, ``"evaluate"`` ->
    seconds under ``fl_stage::evaluate``}, with the rows ``_unscoped`` (ops
    of no part) and ``_total`` (every op once). An op in nested parts is in
    each part's row, so the rows do not sum to ``_total``."""
    out = defaultdict(lambda: defaultdict(float))
    for (stage, pas, parts), s in table(trace, tf_ops).items():
        column = pas if stage == TRAIN else stage
        if column not in PASSES and column != EVALUATE:
            continue
        for row in (*(parts or (UNSCOPED,)), TOTAL):
            out[row][column] += s
    return {row: dict(cols) for row, cols in out.items()}


@functools.lru_cache(maxsize=1)
def _of_run(trace, path):
    return table(trace, _common("stage_common").read_tf_ops(path))


def of_run(ctx):
    """The traced run's table, or None where its trace file is not there."""
    cell = ctx["cell"]
    try:
        path = trace_reduce.find_xplane(os.path.join(
            cell.root, ".bench_cache", "trace", cell.name))
    except FileNotFoundError:
        return None
    return _of_run(ctx["trace"], path)


def ms_per_round(ctx, pas: str):
    """The named pass's device milliseconds per round of the traced window,
    or None where the trace file or an op of that pass is not there."""
    tab = of_run(ctx)
    seconds = by_pass(tab).get(pas) if tab else None
    if not seconds or not ctx["rounds"]:
        return None
    return seconds * 1e3 / ctx["rounds"]


def _share_pct(ctx, part, whole):
    """100 x seconds of the table's rows ``part(key)`` accepts / those
    ``whole(key)`` accepts, or None where the whole is empty."""
    tab = of_run(ctx)
    if not tab:
        return None
    denom = sum(s for k, s in tab.items() if whole(k))
    if denom <= 0:
        return None
    return 100.0 * sum(s for k, s in tab.items() if whole(k) and part(k)) / denom


def unstaged_pct(ctx):
    """Share of the busy self time under no ``fl_stage::``."""
    unstaged = _common("stage_common").UNATTRIBUTED
    return _share_pct(ctx, lambda key: key[0] == unstaged, lambda key: True)


def unscoped_train_pct(ctx):
    """Share of ``local_train``'s self time under no ``fl_layer::``."""
    return _share_pct(ctx, lambda key: not key[2],
                      lambda key: key[0] == TRAIN)
