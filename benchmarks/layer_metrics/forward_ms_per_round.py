"""Client step: device self time per round of the forward pass: the ops under
``fl_stage::local_train`` whose name stack holds ``jvp(`` and neither
``transpose(`` nor ``rematted_computation``: the differentiated forward
(``pass_common.py``)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "pass_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "forward")
