"""Aggregation spine: device self time per round of the ops under the
program's ``fl_stage::server_update`` scope (the strategy's aggregate and
server step), from the ops' metadata in the raw trace file."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "stage_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "server_update")
