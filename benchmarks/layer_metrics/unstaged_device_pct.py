"""Device: share of the busy self time whose ops lie under no ``fl_stage::``
scope of the program (``stage_common.UNATTRIBUTED``): what the stage metrics
cannot place."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "pass_common",
                       ctx["cell"].bench_dir).unstaged_pct(ctx)
