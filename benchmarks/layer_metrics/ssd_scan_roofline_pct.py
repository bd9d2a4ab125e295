"""Kernels: the chunked scan's share of its roofline in the training step.
The least time of a pass is the larger of the chunked form's matmul FLOPs
over the bf16 peak and its least bytes over the HBM peak
(``benchmarks/flops/ssd_scan.py``); a round's least time counts the passes
the trace SHOWS under ``fl_layer::ssd_scan`` in ``fl_stage::local_train``
(forward, the recompute if remat ran one, backward), each clients x local
steps x Mamba blocks times; the metric is that over the measured time of
those same passes. The evaluation forwards are on neither side."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "executed_common",
                       ctx["cell"].bench_dir).train_roofline_pct(
        ctx, "ssd_scan", "ssd_scan")
