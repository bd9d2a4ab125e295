"""Host pipeline: the ``fl::dispatch`` spans per round: what the enqueues of
the round's programs (fit, eval, test) cost the producer thread."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "dispatch")
