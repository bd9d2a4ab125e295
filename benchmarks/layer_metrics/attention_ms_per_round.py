"""Models: device self time per round of the ops under the program's
``fl_layer::attention`` scope: self-attention whole (the q / k / v
projections, the flash calls or the dense scores and softmax, ``o_proj``;
the encoder's and Jamba's; latent attention has
``fl_layer::mla_attention``), forward, recomputed under remat, backward, and
the evaluation forwards."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "attention")
