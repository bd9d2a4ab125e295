"""Host pipeline: the ``fl::prefetch_wait`` spans per round: how long a round
waited in ``RoundPrefetcher.take`` for its batches (the worker's remaining
staging on a hit, the whole synchronous build on a miss)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    return load_module("layer_metrics", "span_common",
                       ctx["cell"].bench_dir).ms_per_round(ctx, "prefetch_wait")
