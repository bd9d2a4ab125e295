"""Kernels: the flash calls' share of their roofline. For every executed
call (a forward recomputed under remat is a call) the least time is the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(``benchmarks/flops/flash_attention.py``); the metric is the sum of least
times over the sum of measured kernel times. At the cells' shapes (T 2,048,
D 64, bf16) every call is compute-bound: 2 to 4 T*T*D-sized dots against 4
to 6 T*D-sized arrays."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    bench_dir = ctx["cell"].bench_dir
    found = load_module("layer_metrics", "flash_common", bench_dir).calls(
        ctx["trace"])
    if not found:
        return None
    fl = load_module("flops", "flash_attention", bench_dir)
    dev = ctx["dev"]
    least = measured = 0.0
    for kind, rows, t, d, item, secs in found:
        least += fl.least_seconds(kind, rows, t, d, dev.bf16_flops_per_s,
                                  dev.hbm_bytes_per_s, item)[0]
        measured += secs
    return 100.0 * least / measured if measured > 0 else None
