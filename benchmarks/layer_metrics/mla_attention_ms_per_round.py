"""Models: device self time per round of the ops under the program's
``fl_layer::mla_attention`` scope: latent attention whole (the five
projections with their adapters, the two inner norms, RoPE, the flash calls
and the transposes around them, forward, recomputed under remat, backward,
and the evaluation forwards)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "mla_attention")
