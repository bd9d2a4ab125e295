"""Shared by the roofline shares that count what a trace SHOWS was executed
under one of the program's ``fl_layer::`` scopes, not what a traffic file
would lead one to expect (PERF.md section 7 (i): a share that counted "the
forward twice under remat" read 1.22 times too high once the recompute was
gone): the training step's device time under the scope by pass, and the
executed Mosaic calls under it by kernel name. A trace without the scope, or
no trace file, gives nothing and raises nothing."""

import os
import re

from benchmarks import trace_reduce
from benchmarks.harness.spec import load_module

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _common(name):
    return load_module("layer_metrics", name, _HERE)


def train_seconds_by_pass(ctx, scope: str) -> dict:
    """pass (forward / recompute / backward / update) -> device self seconds
    of the ops under ``fl_stage::local_train`` whose name stack holds
    ``fl_layer::<scope>``; only the passes that have any."""
    pc = _common("pass_common")
    tab = pc.of_run(ctx) or {}
    out = {}
    for (stage, pas, parts), s in tab.items():
        if stage == pc.TRAIN and scope in parts and s > 0:
            out[pas] = out.get(pas, 0.0) + s
    return out


def train_roofline_pct(ctx, scope: str, flops_module: str):
    """100 x the least time of the training passes the trace shows under the
    scope (``flops/<flops_module>.py least_seconds_per_round(cfg, job, peak
    flops, peak bytes, passes)``) over those passes' measured time, or None
    where the trace has none."""
    by_pass = train_seconds_by_pass(ctx, scope)
    measured = sum(by_pass.values())
    if not measured or not ctx["rounds"]:
        return None
    cell, dev = ctx["cell"], ctx["dev"]
    least = load_module("flops", flops_module, cell.bench_dir
                        ).least_seconds_per_round(
        cell.cfg, cell.job, dev.bf16_flops_per_s, dev.hbm_bytes_per_s,
        sorted(by_pass))
    return 100.0 * least * ctx["rounds"] / measured


def kernel_calls(ctx, scope: str, kernels) -> dict:
    """kernel name -> executed calls in the window (averaged over the chips)
    of the Mosaic calls named so whose name stack holds the scope, whatever
    stage they run in (an evaluation forward is a call)."""
    cell, trace = ctx["cell"], ctx["trace"]
    try:
        path = trace_reduce.find_xplane(os.path.join(
            cell.root, ".bench_cache", "trace", cell.name))
    except FileNotFoundError:
        return {}
    tf_ops = _common("stage_common").read_tf_ops(path)
    layers_of = _common("layer_common").layers_of
    lo, hi = trace.window
    found = {}
    for chip, lane in trace.devices.items():
        names = tf_ops.get(f"/device:TPU:{chip}", {})
        kind_of = {}
        for e in lane.ops:
            if e.end <= lo or e.start >= hi or "tpu_custom_call" not in e.name:
                continue
            if e.name not in kind_of:
                kind_of[e.name] = next(
                    (k for k in kernels
                     if re.search(rf"%\w*{k}_*[.\d]* = ", e.name)
                     and scope in layers_of(names.get(e.name))), None)
            if kind_of[e.name]:
                found[kind_of[e.name]] = found.get(kind_of[e.name], 0) + 1
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in found.items()}
