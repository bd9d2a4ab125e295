"""FLamby Fed-IXI method grid (reference: research/flamby/fed_ixi/ —
3 natural centers (Guys, HH, IOP), binary brain-mask segmentation on T1
MRI volumes; method subdirs apfl/central/ditto/fedadam/fedavg/fedper/
fedprox/fenda/local/moon/perfcl/scaffold).

Synthetic stand-in: 3 centers with FLamby's relative sizes (Guys 249,
HH 145, IOP 74 — scaled), ellipsoid "brain" masks with per-center scanner
shift (intensity gain/offset, anisotropic ellipsoid axes). Real data drops
in via FL4HEALTH_FLAMBY_DIR/fed_ixi.npz (x [N,D,H,W,1] float, y [N,D,H,W]
{0,1}, center [N]).

Run:  python research/flamby/fed_ixi/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/flamby/fed_ixi/sweep.py
"""

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "research" / "flamby"))

import flax.linen as nn
import numpy as np

import common
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models import bases
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
ROUNDS = 2 if TINY else 10
SIZE = 8 if TINY else 16
CENTER_SIZES = (12, 8, 4) if TINY else (62, 36, 18)
FEATS = 4 if TINY else 8


class SegFeatures(nn.Module):
    """3-D conv feature extractor returning a dense feature MAP — the
    split-model bases join/head these per voxel (vs ConvFeatures, which
    flattens for classification heads)."""

    features: int = 8

    @nn.compact
    def __call__(self, x, train: bool = True):
        h = nn.Conv(self.features, (3, 3, 3))(x)
        h = nn.relu(h)
        h = nn.Conv(self.features, (3, 3, 3))(h)
        return nn.relu(h)


def synthetic_ixi():
    rng = np.random.default_rng(13)
    coords = np.stack(
        np.meshgrid(*[np.arange(SIZE)] * 3, indexing="ij"), -1
    ).astype(float)
    xs, ys, cs = [], [], []
    for c, n in enumerate(CENTER_SIZES):
        gain, offset = 1.0 + 0.3 * c, 0.2 * c  # scanner shift per center
        axes_bias = 1.0 + 0.15 * c             # anisotropy per center
        for _ in range(n):
            center = rng.uniform(SIZE * 0.35, SIZE * 0.65, size=3)
            axes = rng.uniform(SIZE * 0.2, SIZE * 0.35, size=3)
            axes[0] *= axes_bias
            d = (((coords - center) / axes) ** 2).sum(-1)
            seg = (d < 1.0).astype(np.int32)
            vol = gain * (seg + rng.normal(0, 0.35, (SIZE,) * 3)) + offset
            xs.append(vol[..., None].astype(np.float32))
            ys.append(seg)
            cs.append(c)
    return np.stack(xs), np.stack(ys), np.asarray(cs)


real = common.real_npz("fed_ixi")
if real is not None:
    x, y, center = real
    print("# data: real FLamby fed_ixi from FL4HEALTH_FLAMBY_DIR")
else:
    x, y, center = synthetic_ixi()
    print("# data: synthetic fed_ixi stand-in (3 centers)")
DATASETS = common.center_datasets(x, y, center)

ZOO = {
    "plain": lambda: bases.SequentiallySplitModel(
        features_module=SegFeatures(FEATS),
        head_module=bases.DenseHead(2),  # per-voxel binary logits
    ),
    "features": lambda: SegFeatures(FEATS),
    "head": lambda: bases.DenseHead(2),
}


def build(seed, method, lr, lam):
    return common.build_method(
        method, ZOO, common.masked_seg_cross_entropy, DATASETS, lr, lam,
        batch_size=4, local_steps=2 if TINY else 4,
        metrics=MetricManager((efficient.segmentation_dice(2),)),
        seed=seed, seg=True,
    )


grid = common.dedup_inert_lam(hp_grid(
    method=list(common.METHODS),
    lr=[0.01] if TINY else [0.003, 0.01, 0.03],
    lam=[0.1] if TINY else [0.01, 0.1, 1.0],
))

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1 if TINY else 3,
    score=lambda history: float(history[-1].eval_metrics["seg_dice"]),
    minimize=False,
)
common.finish(results, "flamby_ixi_", "eval_seg_dice", "dice")
