"""Host pipeline: what the producer thread spends on a round besides waiting
for the device: its ``fl::round`` spans less the ``fl::device_fence`` time
inside them, per round. While the round's two fences stand, everything else
the producer does between them leaves the device idle."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    ms = load_module("layer_metrics", "span_common", ctx["cell"].bench_dir
                     ).less_ms(ctx["trace"], "round", "device_fence")
    return ms / ctx["rounds"] if ms is not None and ctx["rounds"] else None
