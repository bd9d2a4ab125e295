"""Program build, inside the prologue: mean per ``fit()`` call of the
``fl::introspect`` spans within its ``fl::fit_prologue``: the walk over the
round programs' HLO text (``observability/hloscan.py``)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common", ctx["cell"].bench_dir
                       ).mean_ms(ctx["trace"], "introspect", inside="fit_prologue")
