"""Models: device self time per round of the ops under the program's
``fl_layer::mlp`` scope: the dense feed-forward (the encoder's ``ff_in``,
GELU, ``ff_out``; Jamba's SwiGLU in every layer; DeepSeek's layer 0), on
every pass and in the evaluation forwards. The shared experts and the routed
layer have scopes of their own and are not in it."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "mlp")
