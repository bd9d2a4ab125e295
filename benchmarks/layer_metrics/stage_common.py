"""Shared by the two stage metrics: device time by the program's own
``fl_stage::`` scopes.

An ``XLA Ops`` event is named by its HLO text, which holds no scope. The
scope is in the event's *metadata*: a ``tf_op`` stat with the JAX name stack
(``jit(fit_round)/vmap(fl_stage::local_train)/dot_general:``), which
``jax.profiler.ProfileData`` does not expose and ``benchmarks/xplane_meta.py``
reads from the raw file, found from the cell as the harness writes it
(``<root>/.bench_cache/trace/<cell>``). Each op's self time
(``trace_reduce.self_times``: an enclosing ``while`` keeps only what its body
does not cover) goes to the last ``fl_stage::`` of its name stack, else to
``UNATTRIBUTED``; the sum over all of them is the busy self time of
``Trace.op_self_seconds``.
"""

import functools
import os
import re
from collections import defaultdict

from benchmarks import trace_reduce, xplane_meta

STAGE = re.compile(r"fl_stage::([A-Za-z0-9_.\-]+)")
UNATTRIBUTED = "_unattributed"


def stage_of(tf_op):
    hits = STAGE.findall(tf_op) if tf_op else None
    return hits[-1] if hits else UNATTRIBUTED


def by_stage(trace, tf_ops: dict) -> dict:
    """stage -> self seconds inside the window, averaged over the chips of
    the trace. ``tf_ops``: plane name -> {event name: tf_op}."""
    lo, hi = trace.window
    acc = defaultdict(float)
    for chip, lane in trace.devices.items():
        names = tf_ops.get(f"/device:TPU:{chip}", {})
        inside = [e for e in lane.ops if e.end > lo and e.start < hi]
        for e, ns in trace_reduce.self_times(inside):
            acc[stage_of(names.get(e.name))] += ns
    n = max(len(trace.devices), 1)
    return {k: v / 1e9 / n for k, v in acc.items()}


def read_tf_ops(path: str) -> dict:
    return {plane: xplane_meta.by_name(pairs)
            for plane, pairs in xplane_meta.tf_ops(path).items()}


@functools.lru_cache(maxsize=1)
def _of_run(trace, path):
    return by_stage(trace, read_tf_ops(path))


def ms_per_round(ctx, stage: str):
    """The named stage's device milliseconds per round of the traced window,
    or None where the run's trace file or the stage is not there."""
    cell = ctx["cell"]
    try:
        path = trace_reduce.find_xplane(os.path.join(
            cell.root, ".bench_cache", "trace", cell.name))
    except FileNotFoundError:
        return None
    seconds = _of_run(ctx["trace"], path).get(stage)
    if not seconds or not ctx["rounds"]:
        return None
    return seconds * 1e3 / ctx["rounds"]
