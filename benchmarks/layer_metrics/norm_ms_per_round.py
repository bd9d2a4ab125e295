"""Models: device self time per round of the ops under the program's
``fl_layer::norm`` scope: LayerNorm / RMSNorm wherever one lies (the blocks'
two, the final one, the inner norms of the Mamba mixer and of latent
attention, which count for those parts too), as far as XLA left a norm an op
of its own: one fused into a matmul counts with the matmul's part."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "norm")
