"""Models: device self time per round of the ops under the program's
``fl_layer::moe_router`` scope: the routed layer's router (float32 logits
over all the layer's experts, softmax, the group maximum, two ``top_k``), a
part of ``moe_ms_per_round``."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "moe_router")
