"""Required FLOPs of the steps completed (analytic, ``flops/<family>.py``:
forward and backward, no recomputation) over the window's wall, the chips
and the chip's bf16 peak from ``peaks.json``."""


def read(ctx):
    return (100.0 * ctx["step_flops"] * ctx["steps"] / ctx["wall_s"]
            / ctx["chips"] / ctx["dev"].bf16_flops_per_s)
