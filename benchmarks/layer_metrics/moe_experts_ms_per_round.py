"""Models: device self time per round of the ops under the program's
``fl_layer::moe_experts`` scope: the held routed experts' grouped products
(``models/deepseek.py routed_experts`` through an expert body: forward, the
recompute under remat, the backward's tiles with the forward they rebuild,
the evaluation forwards); a part of ``moe_ms_per_round``, beside
``moe_router_ms_per_round``: what is left of ``moe`` is the plan's sort,
gathers and scatters. A program without the scope gives nothing."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "moe_experts")
