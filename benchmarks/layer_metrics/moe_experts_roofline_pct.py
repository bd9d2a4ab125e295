"""Kernels: the held routed experts' grouped products' share of their
roofline. The least time of a round is the larger of the expected
assignments' FLOPs over the bf16 peak and the bytes of the held experts (read
once for a step's forward and once for its backward) and of their rows over
the HBM peak (``benchmarks/flops/moe_experts.py``; at ~154 rows an expert the
bytes bound it); the metric is that over the measured time under
``fl_layer::moe_experts`` (the products alone: forward, recomputed under
remat, recomputed again and transposed in the backward's tiles, and the
evaluation forwards, none of which the least time counts)."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    cell = ctx["cell"]
    measured = load_module("layer_metrics", "layer_common",
                           cell.bench_dir).seconds(ctx, "moe_experts")
    if not measured or not ctx["rounds"]:
        return None
    dev = ctx["dev"]
    least, _ = load_module("flops", "moe_experts", cell.bench_dir
                           ).least_seconds_per_round(
        cell.cfg, cell.job, dev.bf16_flops_per_s, dev.hbm_bytes_per_s)
    return 100.0 * least * ctx["rounds"] / measured
