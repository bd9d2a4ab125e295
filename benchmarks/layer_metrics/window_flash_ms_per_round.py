"""Kernels: device self time per round of the ops under the program's
``fl_layer::window_flash`` scope: a sliding-window layer's three flash calls
(``flash_attention(window=...)``: forward, dQ, dK/dV, the evaluation
forwards too) and what surrounds them (delta's reduce, the kept ``out``'s
rounding, the statistics' relayouts); a part of ``attention_ms_per_round``.
A program without the scope gives nothing."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "window_flash")
