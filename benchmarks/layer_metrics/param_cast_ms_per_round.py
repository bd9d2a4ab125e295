"""Client step: device self time per round of the ops under the program's
``fl_layer::param_cast`` scope: the float32 master parameters cast to the
compute type (``precision/policy.py cast_model_def``;
``transformer.LoraDense`` for a module with a ``dtype`` of its own) and the
cast's transpose, as far as XLA left them ops of their own: a cast fused
into its matmul counts there."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "param_cast")
