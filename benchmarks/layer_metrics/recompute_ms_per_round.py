"""Client step: device self time per round of the recompute pass: the ops under
``fl_stage::local_train`` whose name stack holds ``rematted_computation``:
what ``jax.checkpoint`` / ``nn.remat`` runs again on the way back; None in a
cell without remat (``pass_common.py``)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "pass_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "recompute")
