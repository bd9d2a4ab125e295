"""Models: device self time per round of the ops under the program's
``fl_layer::moe_latent`` scope: the two projections between the model's
width and the latent the routed experts work in, with their adapters; a part
of ``moe_ms_per_round``."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "moe_latent")
