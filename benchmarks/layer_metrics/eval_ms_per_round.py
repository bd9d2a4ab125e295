"""Client step: device self time per round of the ops under the program's
``fl_stage::evaluate`` scope: the evaluation round's vmapped client part
(the clients' pull of the global weights and their forwards over the
validation batches), from the ops' metadata in the raw trace file."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "stage_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "evaluate")
