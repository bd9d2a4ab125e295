"""Kernels: the held three-matrix routed experts' grouped products' share of
their roofline in the training step, where the configuration names its share
as ``models/afmoe.py`` does. The least time of a pass is the larger of the
expected assignments' FLOPs over the bf16 peak and the bytes of the held
experts (read once a pass) and of their rows over the HBM peak
(``benchmarks/flops/swiglu_experts.py``; at ~1,500 rows an expert the
operations bound it); a round's least time counts the passes the trace SHOWS
under ``fl_layer::moe_experts`` in ``fl_stage::local_train`` (forward, the
recompute if remat ran one, backward), each local steps x expert layers
times (the clients are one call's rows); the metric is that over the
measured time of those same passes, which holds the forward that the
backward's tiles recompute. A program without the scope gives nothing."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "executed_common",
                       ctx["cell"].bench_dir).train_roofline_pct(
        ctx, "moe_experts", "swiglu_experts")
