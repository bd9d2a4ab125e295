"""Kernels: the latent-attention flash calls' share of their roofline. The
least time of a round is, for every training call (forward, the forward
recomputed under remat, dQ, dK/dV; clients x local steps x layers), the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak at the
widths the mathematics has (S at 192, P V at 128, the causal triangle:
``benchmarks/flops/mla_flash.py``); the metric is that over the measured time
under ``fl_layer::mla_flash`` (the calls and the transposes and pads around
them). The kernels run at 256 / 128 lanes and execute 3 of 4 tiles at T 1,024
with blocks of 512: both show here as a lower share."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    cell = ctx["cell"]
    measured = load_module("layer_metrics", "layer_common",
                           cell.bench_dir).seconds(ctx, "mla_flash")
    if not measured or not ctx["rounds"]:
        return None
    dev = ctx["dev"]
    least = load_module("flops", "mla_flash", cell.bench_dir
                        ).least_seconds_per_round(
        cell.cfg, cell.job, dev.bf16_flops_per_s, dev.hbm_bytes_per_s)
    return 100.0 * least * ctx["rounds"] / measured
