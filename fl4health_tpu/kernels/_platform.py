"""Where a Pallas kernel runs: Mosaic on the TPU, the interpreter on CPU."""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Interpret mode is for the CPU test lane; the TPU compiles through
    Mosaic. Any other backend is an error: these kernels were written and
    checked for exactly those two, and a third one must not run some
    unverified path under a kernel's name."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels support the 'tpu' (compiled) and 'cpu' (interpret) "
        f"backends; the default backend is {platform!r}. Pass interpret= "
        "explicitly if you know this backend can run them."
    )
