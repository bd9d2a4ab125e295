"""FLamby Fed-ISIC2019 method grid (reference:
research/flamby/fed_isic2019/ — 6 natural centers, 8-class dermoscopy
images, severe per-center label skew; method subdirs include the base grid
plus ditto_mkmmd / ditto_deep_mmd / mr_mtl_mkmmd / mr_mtl_deep_mmd).

Synthetic stand-in: 6 centers with FLamby's extreme size imbalance (BCN
12413, ViDIR-group 3954/3363, MSK 819, ViDIR-molemax 439, rosendahl 225 —
scaled), class prototypes in image space, and per-center label-marginal
skew + acquisition shift (brightness/contrast per center). Real data drops
in via FL4HEALTH_FLAMBY_DIR/fed_isic2019.npz (x [N,H,W,3] float, y [N]
{0..7}, center [N]).

Run:  python research/flamby/fed_isic2019/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/flamby/fed_isic2019/sweep.py
"""

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "research" / "flamby"))

import numpy as np

import common
from fl4health_tpu.clients import engine
from fl4health_tpu.clients.ditto import KeepLocalExchanger
from fl4health_tpu.clients.mmd import (
    DittoMkMmdClientLogic,
    MrMtlDeepMmdClientLogic,
    MrMtlMkMmdClientLogic,
)
from fl4health_tpu.exchange.exchanger import FixedLayerExchanger
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models import bases
from fl4health_tpu.server.simulation import FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
ROUNDS = 2 if TINY else 12
N_CLASSES = 8
HW = 8 if TINY else 24
CHANNELS = (4, 8) if TINY else (8, 16)
CENTER_SIZES = (48, 24, 20, 12, 8, 8) if TINY else (1240, 395, 336, 82, 44, 24)
FEATURE_DIM = (HW // 4) ** 2 * CHANNELS[-1]  # ConvFeatures: two 2x2 pools


def synthetic_isic():
    rng = np.random.default_rng(11)
    protos = rng.normal(scale=1.2, size=(N_CLASSES, HW, HW, 3))
    xs, ys, cs = [], [], []
    for c, n in enumerate(CENTER_SIZES):
        # per-center label marginal: Dirichlet skew, heavier at small centers
        marginal = rng.dirichlet([2.0 / (1 + c)] * N_CLASSES)
        y = rng.choice(N_CLASSES, size=n, p=marginal)
        x = protos[y] + rng.normal(scale=1.0, size=(n, HW, HW, 3))
        x = x * rng.uniform(0.8, 1.2) + rng.normal(scale=0.3)  # acquisition
        xs.append(x.astype(np.float32))
        ys.append(y.astype(np.int64))
        cs.append(np.full(n, c))
    return np.concatenate(xs), np.concatenate(ys), np.concatenate(cs)


real = common.real_npz("fed_isic2019")
if real is not None:
    x, y, center = real
    print("# data: real FLamby fed_isic2019 from FL4HEALTH_FLAMBY_DIR")
else:
    x, y, center = synthetic_isic()
    print("# data: synthetic fed_isic2019 stand-in (6 skewed centers)")
DATASETS = common.center_datasets(x, y, center)

ZOO = {
    "plain": lambda: bases.SequentiallySplitModel(
        features_module=bases.ConvFeatures(channels=CHANNELS),
        head_module=bases.DenseHead(N_CLASSES),
    ),
    "features": lambda: bases.ConvFeatures(channels=CHANNELS),
    "head": lambda: bases.DenseHead(N_CLASSES),
}
# FLamby scores ISIC with balanced accuracy (severe class imbalance)
METRICS = lambda: MetricManager(  # noqa: E731
    (efficient.balanced_accuracy(N_CLASSES),)
)
MMD_METHODS = ("ditto_mkmmd", "mr_mtl_mkmmd", "mr_mtl_deep_mmd")


def build(seed, method, lr, lam):
    import optax

    if method not in MMD_METHODS:
        return common.build_method(
            method, ZOO, engine.masked_cross_entropy, DATASETS, lr, lam,
            batch_size=8, local_steps=2 if TINY else 4, metrics=METRICS(),
            seed=seed,
        )
    if method == "ditto_mkmmd":
        model = bases.TwinModel(global_model=ZOO["plain"](),
                                personal_model=ZOO["plain"]())
        logic = DittoMkMmdClientLogic(
            engine.from_flax(model), engine.masked_cross_entropy,
            feature_model=engine.from_flax(ZOO["plain"]()),
            lam=lam, mkmmd_loss_weight=1.0, beta_global_update_interval=2,
        )
        exchanger = FixedLayerExchanger(bases.TwinModel.exchange_global_model)
    elif method == "mr_mtl_mkmmd":
        logic = MrMtlMkMmdClientLogic(
            engine.from_flax(ZOO["plain"]()), engine.masked_cross_entropy,
            lam=lam, mkmmd_loss_weight=1.0, beta_global_update_interval=2,
        )
        exchanger = KeepLocalExchanger()
    else:  # mr_mtl_deep_mmd
        logic = MrMtlDeepMmdClientLogic(
            engine.from_flax(ZOO["plain"]()), engine.masked_cross_entropy,
            feature_sizes={"features": FEATURE_DIM},
            lam=lam, deep_mmd_loss_weight=1.0, optimization_steps=1,
            mmd_kernel_train_interval=2,
        )
        exchanger = KeepLocalExchanger()
    return FederatedSimulation(
        logic=logic,
        tx=optax.adam(lr),
        strategy=FedAvg(),
        datasets=DATASETS,
        batch_size=8,
        metrics=METRICS(),
        local_steps=2 if TINY else 4,
        seed=seed,
        exchanger=exchanger,
        extra_loss_keys=tuple(getattr(logic, "extra_loss_keys", ()) or ()),
    )


grid = common.dedup_inert_lam(hp_grid(
    method=list(common.METHODS) + list(MMD_METHODS),
    lr=[0.003] if TINY else [0.001, 0.003, 0.01],
    lam=[0.1] if TINY else [0.01, 0.1, 1.0],
), extra_lam_methods=MMD_METHODS)

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1 if TINY else 3,
    score=lambda history: float(
        history[-1].eval_metrics["balanced_accuracy"]
    ),
    minimize=False,
)
common.finish(results, "flamby_isic_", "eval_balanced_accuracy",
              "balanced_accuracy")
