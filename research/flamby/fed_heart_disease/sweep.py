"""FLamby Fed-Heart-Disease method grid (reference:
research/flamby/fed_heart_disease/ — 4 natural centers, 13 tabular
features, binary target; method subdirs apfl/central/ditto/fedadam/fedavg/
fedper/fedprox/fenda/local/moon/perfcl/scaffold with Slurm HP sweeps and
find_best_hp.py selection).

Synthetic stand-in: 4 centers with FLamby's relative sizes (Cleveland 303,
Hungarian 261, Switzerland 46, Long Beach VA 130 — scaled), a shared linear
risk rule, and per-center covariate shift + label noise so personalization
arms have signal to exploit. Real data drops in via
FL4HEALTH_FLAMBY_DIR/fed_heart_disease.npz (x [N,13] float, y [N] {0,1},
center [N]).

Run:  python research/flamby/fed_heart_disease/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/flamby/fed_heart_disease/sweep.py
"""

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "research" / "flamby"))

import numpy as np

import common
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models import bases
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
ROUNDS = 2 if TINY else 15
CENTER_SIZES = (40, 34, 12, 20) if TINY else (303, 261, 46, 130)
N_FEATURES = 13


def synthetic_heart():
    rng = np.random.default_rng(7)
    w = rng.normal(size=N_FEATURES)
    xs, ys, cs = [], [], []
    for c, n in enumerate(CENTER_SIZES):
        shift = rng.normal(scale=0.6, size=N_FEATURES)  # covariate shift
        x = rng.normal(size=(n, N_FEATURES)) + shift
        logits = x @ w + rng.normal(scale=1.0, size=n)
        y = (logits > np.median(logits)).astype(np.int64)
        # center-specific label noise (annotation-protocol heterogeneity)
        flip = rng.random(n) < (0.02 + 0.04 * c)
        y = np.where(flip, 1 - y, y)
        xs.append(x.astype(np.float32))
        ys.append(y)
        cs.append(np.full(n, c))
    return np.concatenate(xs), np.concatenate(ys), np.concatenate(cs)


real = common.real_npz("fed_heart_disease")
if real is not None:
    x, y, center = real
    print("# data: real FLamby fed_heart_disease from FL4HEALTH_FLAMBY_DIR")
else:
    x, y, center = synthetic_heart()
    print("# data: synthetic fed_heart_disease stand-in (4 centers)")
DATASETS = common.center_datasets(x, y, center)

ZOO = {
    # FLamby's heart-disease baseline is logistic regression; the split
    # arms need a features/head factorization, so the grid's backbone is a
    # small MLP with a matching linear head.
    "plain": lambda: bases.SequentiallySplitModel(
        features_module=bases.DenseFeatures((16,)),
        head_module=bases.DenseHead(2),
    ),
    "features": lambda: bases.DenseFeatures((16,)),
    "head": lambda: bases.DenseHead(2),
}


def build(seed, method, lr, lam):
    from fl4health_tpu.clients import engine

    return common.build_method(
        method, ZOO, engine.masked_cross_entropy, DATASETS, lr, lam,
        batch_size=8, local_steps=2 if TINY else 4,
        metrics=MetricManager((efficient.accuracy(),)), seed=seed,
    )


grid = common.dedup_inert_lam(hp_grid(
    method=list(common.METHODS),
    lr=[0.01] if TINY else [0.003, 0.01, 0.03],
    lam=[0.1] if TINY else [0.01, 0.1, 1.0],
))

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1 if TINY else 3,
    score=lambda history: float(history[-1].eval_metrics["accuracy"]),
    minimize=False,
)
common.finish(results, "flamby_heart_", "eval_accuracy", "accuracy")
