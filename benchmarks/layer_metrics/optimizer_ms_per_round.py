"""Client step: device self time per round of the ops under the program's
``fl_layer::optimizer`` scope: ``tx.update``, ``optax.apply_updates`` and
the selects that make a padding step a no-op (``clients/engine.py
make_train_step``): passes over the clients' parameters, once a local step."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "layer_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "optimizer")
