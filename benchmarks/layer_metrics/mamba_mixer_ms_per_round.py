"""Models: device self time per round of the ops under the program's
``fl_layer::mamba_mixer`` scope: the Mamba mixer whole (``in_proj``, the
causal conv, ``x_proj``, the three inner norms, ``dt_proj``, the selective
scan, ``out_proj``), on every pass and in the evaluation forwards;
``ssm_scan_ms_per_round`` is the scan's part of it."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "mamba_mixer")
