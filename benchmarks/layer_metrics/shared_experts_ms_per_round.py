"""Models: device self time per round of the ops under the program's
``fl_layer::shared_experts`` scope: the shared experts' SwiGLU beside the
routed layer, on every pass and in the evaluation forwards."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "shared_experts")
