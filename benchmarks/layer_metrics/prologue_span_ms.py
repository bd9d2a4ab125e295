"""Entry layer, from inside: mean of the program's own ``fl::fit_prologue``
span per ``fit()`` call, from the call's entry to just before its first
round. The inside twin of ``fit_prologue_ms``, which is timed from outside
(the harness's span to the first device launch)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common",
                       ctx["cell"].bench_dir).mean_ms(ctx["trace"], "fit_prologue")
