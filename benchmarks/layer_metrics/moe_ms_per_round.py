"""Models: device self time per round of the ops under the program's
``fl_layer::moe`` scope: the routed layer (the router in float32, the sort of
the assignments by held expert, the tile loops with their gathers, grouped
products and scatter-adds, the combine), without the shared experts beside
it (``fl_layer::shared_experts``). The grouped products' own part is what
``moe_experts_roofline_pct`` divides by."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "moe")
