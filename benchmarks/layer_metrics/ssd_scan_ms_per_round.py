"""Kernels: device self time per round of the ops under the program's
``fl_layer::ssd_scan`` scope: the chunked scalar-decay recurrence alone, from
the split of ``xBC`` to ``y`` before the gated norm (kernels/ssd_scan.py: no
Mosaic call, XLA's batched matmuls, exponentials and a scan over chunks); a
part of ``ssd_mixer_ms_per_round``."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "ssd_scan")
