"""Shared by the ``fl_layer::`` metrics: device time of the ops inside one of
the program's ``fl_layer::<name>`` scopes (``fl_layer::ssm_scan``,
``fl_layer::mamba_mixer``, ``fl_layer::attention``, ``fl_layer::shared_cast``).

Unlike a stage (``stage_common.py`` gives an op to the *last* ``fl_stage::``
of its name stack), layer scopes nest (the scan lies inside the mixer), so an
op counts for every layer whose scope its name stack *holds*. The name stack
is the ``tf_op`` stat of the op's metadata, read from the raw trace file by
``benchmarks/xplane_meta.py``; an op's time is its self time
(``trace_reduce.self_times``: a ``while`` keeps only what its body does not
cover, and the body's ops carry the scope themselves). A program without the
scope gives ``None``.
"""

import functools
import os
from collections import defaultdict

from benchmarks import trace_reduce, xplane_meta

PREFIX = "fl_layer::"


def layers_of(tf_op) -> set:
    if not tf_op or PREFIX not in tf_op:
        return set()
    out = set()
    for part in tf_op.split(PREFIX)[1:]:
        name = part.split("/", 1)[0].split(")", 1)[0].split(":", 1)[0]
        if name:
            out.add(name)
    return out


def by_layer(trace, tf_ops: dict) -> dict:
    """layer -> self seconds inside the window, averaged over the chips of
    the trace. ``tf_ops``: plane name -> {event name: tf_op}."""
    lo, hi = trace.window
    acc = defaultdict(float)
    for chip, lane in trace.devices.items():
        names = tf_ops.get(f"/device:TPU:{chip}", {})
        inside = [e for e in lane.ops if e.end > lo and e.start < hi]
        for e, ns in trace_reduce.self_times(inside):
            for layer in layers_of(names.get(e.name)):
                acc[layer] += ns
    n = max(len(trace.devices), 1)
    return {k: v / 1e9 / n for k, v in acc.items()}


def read_tf_ops(path: str) -> dict:
    return {plane: xplane_meta.by_name(pairs)
            for plane, pairs in xplane_meta.tf_ops(path).items()}


@functools.lru_cache(maxsize=1)
def _of_run(trace, path):
    return by_layer(trace, read_tf_ops(path))


def seconds(ctx, layer: str):
    """The named layer's device seconds in the traced window, or None where
    the run's trace file or the scope is not there."""
    cell = ctx["cell"]
    try:
        path = trace_reduce.find_xplane(os.path.join(
            cell.root, ".bench_cache", "trace", cell.name))
    except FileNotFoundError:
        return None
    return _of_run(ctx["trace"], path).get(layer) or None


def ms_per_round(ctx, layer: str):
    total = seconds(ctx, layer)
    if not total or not ctx["rounds"]:
        return None
    return total * 1e3 / ctx["rounds"]
