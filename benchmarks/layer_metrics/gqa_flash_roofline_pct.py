"""Kernels: the grouped-query flash calls' share of their roofline. For
every executed call the trace SHOWS under ``fl_layer::gqa_flash`` (forward,
dQ, dK/dV by the kernels' names; an evaluation forward is a call, a
recompute that remat spared is none) the least time is the larger of the
causal triangle's FLOPs at 32 query heads of 128 over the bf16 peak and the
call's least bytes, with the 2 key/value heads read once a group, over the
HBM peak (``benchmarks/flops/gqa_flash.py``); the metric is their sum over
the measured time under the scope (the three calls and what surrounds them:
delta's reduce, the kept ``out``'s rounding, the statistics' relayouts)."""

KERNELS = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}


def read(ctx):
    from benchmarks.harness.spec import load_module

    cell = ctx["cell"]
    measured = load_module("layer_metrics", "layer_common",
                           cell.bench_dir).seconds(ctx, "gqa_flash")
    if not measured:
        return None
    calls = load_module("layer_metrics", "executed_common", cell.bench_dir
                        ).kernel_calls(ctx, "gqa_flash", sorted(KERNELS))
    if not calls:
        return None
    dev = ctx["dev"]
    least = load_module("flops", "gqa_flash", cell.bench_dir
                        ).least_seconds_of_calls(
        cell.cfg, cell.job, {KERNELS[k]: n for k, n in calls.items()},
        dev.bf16_flops_per_s, dev.hbm_bytes_per_s)
    return 100.0 * least / measured
