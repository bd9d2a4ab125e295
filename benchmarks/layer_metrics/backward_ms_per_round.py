"""Client step: device self time per round of the backward pass: the ops under
``fl_stage::local_train`` whose name stack holds ``transpose(`` and no
``rematted_computation``: the cotangents' way back, the ``custom_vjp``
backward kernels among them (``pass_common.py``)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "pass_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "backward")
