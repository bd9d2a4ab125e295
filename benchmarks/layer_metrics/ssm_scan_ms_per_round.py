"""Kernels: device self time per round of the ops under the program's
``fl_layer::ssm_scan`` scope: the selective scan alone (forward, the forward
recomputed under remat, the backward with its own recomputation), without
the mixer's projections, conv and norms around it."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "ssm_scan")
