"""Kernels: the sliding-window flash calls' share of their roofline. For
every executed call the trace SHOWS under ``fl_layer::window_flash``
(forward, dQ, dK/dV by the kernels' names; an evaluation forward is a call,
a recompute that remat spared is none) the least time is the larger of the
IN-WINDOW scores' FLOPs (``W*T - W*(W-1)/2`` a query head, not the tiles a
kernel executes) at the bf16 peak and the call's least bytes, with the
key/value heads read once a group, over the HBM peak
(``benchmarks/flops/window_flash.py``); the metric is their sum over the
measured time under the scope (the three calls and what surrounds them:
delta's reduce, the kept ``out``'s rounding, the statistics' relayouts). It
counts required scores, so it reads under 100 % however a kernel skips: one
that executed every causal tile would read lower, not higher. A program
without the scope gives nothing."""

KERNELS = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}


def read(ctx):
    from benchmarks.harness.spec import load_module

    cell = ctx["cell"]
    measured = load_module("layer_metrics", "layer_common",
                           cell.bench_dir).seconds(ctx, "window_flash")
    if not measured:
        return None
    calls = load_module("layer_metrics", "executed_common", cell.bench_dir
                        ).kernel_calls(ctx, "window_flash", sorted(KERNELS))
    if not calls:
        return None
    dev = ctx["dev"]
    least = load_module("flops", "window_flash", cell.bench_dir
                        ).least_seconds_of_calls(
        cell.cfg, cell.job, {KERNELS[k]: n for k, n in calls.items()},
        dev.bf16_flops_per_s, dev.hbm_bytes_per_s)
    return 100.0 * least / measured
