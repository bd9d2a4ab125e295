"""Kernels: the selective scan's share of its memory roofline. The least
time of a round is the bytes every executed pass of the scan must move
(``benchmarks/flops/selective_scan.py``: x, Delta, z, y once each at the
compute type, B and C in float32; a backward pass reads four and writes three
of the large arrays) over the chip's HBM bandwidth; the metric is that over
the measured time under ``fl_layer::ssm_scan``. ``peaks.json`` holds no
vector-unit peak, so this is a share of the MEMORY roofline only: a scan that
read 100 here would still be bound by its exponentials and multiplies."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    cell = ctx["cell"]
    measured = load_module("layer_metrics", "layer_common",
                           cell.bench_dir).seconds(ctx, "ssm_scan")
    if not measured or not ctx["rounds"]:
        return None
    least = load_module("flops", "selective_scan", cell.bench_dir
                        ).least_seconds_per_round(
        cell.cfg, cell.job, ctx["dev"].hbm_bytes_per_s) * ctx["rounds"]
    return 100.0 * least / measured
