"""Program build, inside the prologue: mean per ``fit()`` call of the
``fl::introspect`` spans within its ``fl::fit_prologue``: the capture of the
round programs' reports (``observability/introspect.py``: lower, cache load,
``cost_analysis`` / ``memory_analysis``) in a simulation's first call,
milliseconds of argument building in later ones, which record the remembered
reports."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common", ctx["cell"].bench_dir
                       ).mean_ms(ctx["trace"], "introspect", inside="fit_prologue")
