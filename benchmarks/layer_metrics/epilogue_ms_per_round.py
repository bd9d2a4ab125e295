"""Host pipeline: the consumer thread's ``fl::epilogue`` spans per round:
the fused device-to-host pull, records, checkpoints and reporters. Under a
round's device time it is hidden; above it the consumer sets the pace."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "epilogue")
