"""Client step: device self time per round of the ops under
``fl_layer::shared_cast``: the shared base's matrices cast to the compute
type, once a round in each round program, outside the client vmap and the
local-step scan."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "shared_cast")
