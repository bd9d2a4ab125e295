"""Pallas TPU kernels for hot ops (interpret-mode fallback elsewhere)."""

from fl4health_tpu.kernels.dp_clip import (
    fused_clipped_masked_sum,
    per_example_sq_norms,
    scaled_masked_sum,
)
from fl4health_tpu.kernels.flash_attention import (
    flash_attention,
    flash_attention_lse,
)
from fl4health_tpu.kernels.selective_scan import selective_scan

__all__ = [
    "fused_clipped_masked_sum",
    "per_example_sq_norms",
    "scaled_masked_sum",
    "flash_attention",
    "flash_attention_lse",
    "selective_scan",
]
