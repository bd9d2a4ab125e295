"""From the profiler's trace of the window to the per-layer readings: load
the trace, hand it with the run's counters and spans to each metric's own
reader (``layer_metrics/<name>.py``, found by the metric's name), and build
the breakdown."""

from __future__ import annotations

import os

from .spec import Cell, load_module


def program_files(root: str) -> frozenset:
    """Base names of the program's own source files: idle gaps are attributed
    to the innermost frame that lies in one of them."""
    names = set()
    for _, _, files in os.walk(os.path.join(root, "fl4health_tpu")):
        names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def top(d: dict, n: int = 10):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def layer_readings(cell: Cell, trace_dir: str, ctx: dict):
    """(name -> value or None, busy_s, window_s, breakdown)."""
    reduce = load_module("", "trace_reduce", cell.bench_dir)
    trace = reduce.load(reduce.find_xplane(trace_dir))
    if not trace.devices:
        raise RuntimeError("the trace holds no /device:TPU plane")
    ctx = dict(ctx, trace=trace)
    readings = {}
    for m in cell.metrics("per_layer"):
        reader = load_module("layer_metrics", m["name"], cell.bench_dir)
        readings[m["name"]] = reader.read(ctx)
    breakdown = {
        "device_ops": top(trace.op_self_seconds()),
        "idle_gaps": top(trace.idle_by_frame(program_files(cell.root))),
    }
    return readings, trace.busy_s(), trace.window_s(), breakdown
