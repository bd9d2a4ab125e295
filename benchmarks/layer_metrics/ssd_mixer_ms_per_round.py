"""Models: device self time per round of the ops under the program's
``fl_layer::ssd_mixer`` scope: the Mamba-2 mixer whole (``in_proj``, the
conv, the chunked scan, the gated group norm, ``out_proj`` and their
adapters), training passes and evaluation forwards alike."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "ssd_mixer")
