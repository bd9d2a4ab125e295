"""Kernels: summed device time of the three Mosaic flash calls (forward,
recomputed forward, dQ, dK/dV) per round of the traced window."""


def read(ctx):
    from benchmarks.harness.spec import load_module

    found = load_module("layer_metrics", "flash_common",
                        ctx["cell"].bench_dir).calls(ctx["trace"])
    if not found or not ctx["rounds"]:
        return None
    return sum(c[-1] for c in found) * 1e3 / ctx["rounds"]
