"""Models: device self time per round of the ops under the program's
``fl_layer::head`` scope: what consumes the final norm: mean pooling or the
last-token gather, the classifier / ``score`` product, and their gradients."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "head")
