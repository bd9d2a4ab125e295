"""Models: device self time per round of the ops under the program's
``fl_layer::embed`` scope: the token (and position) embedding's gather, its
cast and the gradient's scatter-add."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "embed")
