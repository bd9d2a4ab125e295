"""Models: device self time per round of the ops under the program's
``fl_layer::lora`` scope: the adapter branch of every adapted projection
(``x A``, ``. B``, the scale and their gradients; not the base product):
``decoder_common.lora_dense``."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "lora")
