"""Client step: share of ``fl_stage::local_train``'s self time whose ops lie
under no ``fl_layer::`` scope: what the part metrics cannot place (residual
adds, the loss and the meters, the batches' slices, the scan's bookkeeping)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "pass_common",
                       ctx["cell"].bench_dir).unscoped_train_pct(ctx)
