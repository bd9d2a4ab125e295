"""rxrx1 personalization experiments (reference: research/rxrx1/ — fedavg /
ditto / mr_mtl (+ MMD-regularized variants) on the RxRx1 fluorescence
microscopy corpus partitioned by experiment site, selected by
find_best_hp).

Real data rides `datasets.medical.load_rxrx1_data` when
FL4HEALTH_RXRX1_DIR points at the reference's on-disk layout
(metadata.csv + images/*.npy); without it (zero-egress box) the corpus is
synthetic microscopy-shaped images with per-site covariate shift — the same
experiment shape at toy scale. The MMD arm exercises DittoMkMmdClientLogic,
the reference's ditto_mkmmd variant.

Run:  python research/rxrx1/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/rxrx1/sweep.py
"""

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))

import jax
import numpy as np
import optax

from fl4health_tpu.clients import engine
from fl4health_tpu.clients.personalized import (
    KeepLocalExchanger,
    PersonalizedMode,
    exchange_global_subtree,
    make_it_personal,
)
from fl4health_tpu.exchange.exchanger import FixedLayerExchanger
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import MnistNet
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_SITES = 2 if TINY else 4
ROUNDS = 2 if TINY else 8
PER_SITE = 24 if TINY else 160
HW = 12 if TINY else 28
CLASSES = 4 if TINY else 10


def _synth_site(site: int):
    """Microscopy-shaped synthetic: class = blob count pattern, site =
    global intensity/illumination shift (the covariate shift rxrx1's
    site partition exists to study)."""
    rng = np.random.default_rng(31 + site)
    x = np.zeros((PER_SITE, HW, HW, 1), np.float32)
    y = rng.integers(0, CLASSES, PER_SITE).astype(np.int32)
    coords = np.stack(np.meshgrid(np.arange(HW), np.arange(HW),
                                  indexing="ij"), -1)
    for i in range(PER_SITE):
        img = rng.normal(0.1 * site, 0.15, (HW, HW))
        for _ in range(int(y[i]) + 1):
            c = rng.uniform(2, HW - 2, 2)
            r = rng.uniform(1.0, 2.0)
            img += np.exp(-np.sum((coords - c) ** 2, -1) / (2 * r * r))
        x[i, ..., 0] = img * (1.0 + 0.2 * site)
    return x, y


def _load_sites():
    """-> (sites, n_classes). The label space comes from the DATA: real
    rxrx1 has ~1108 siRNA classes (load_rxrx1_data's info), and the model
    head must be sized from it, not from the synthetic default."""
    data_dir = os.environ.get("FL4HEALTH_RXRX1_DIR")
    if data_dir and Path(data_dir).exists():
        from fl4health_tpu.datasets.medical import load_rxrx1_data

        sites, n_classes = [], None
        for s in range(1, N_SITES + 1):
            try:
                x, y, info = load_rxrx1_data(data_dir, client_site=s,
                                             train=True)
                sites.append((x, y))
                n_classes = int(info["n_classes"])
            except FileNotFoundError:
                break
        if sites:
            print(f"# data: real rxrx1 from {data_dir} ({len(sites)} sites, "
                  f"{n_classes} classes)")
            return sites, n_classes
    print("# data: synthetic microscopy-shaped corpus with site shift")
    return [_synth_site(s) for s in range(N_SITES)], CLASSES


def client_datasets() -> tuple[list[ClientDataset], int]:
    sites, n_classes = _load_sites()
    out = []
    for x, y in sites:
        cut = int(len(x) * 0.75)
        out.append(ClientDataset(x[:cut], y[:cut], x[cut:], y[cut:]))
    return out, n_classes


DATASETS, N_CLASSES_DATA = client_datasets()


def build(seed: int, algo: str, lr: float, lam: float) -> FederatedSimulation:
    model = engine.from_flax(MnistNet(n_classes=N_CLASSES_DATA, hidden=32))
    extra_keys = ()
    if algo == "ditto_mkmmd":
        from fl4health_tpu.clients.mmd import DittoMkMmdClientLogic
        from fl4health_tpu.models import bases

        def _net():
            return MnistNet(n_classes=N_CLASSES_DATA, hidden=32)

        twin = bases.TwinModel(global_model=_net(), personal_model=_net())
        logic = DittoMkMmdClientLogic(
            engine.from_flax(twin), engine.masked_cross_entropy,
            feature_model=engine.from_flax(_net()),
            lam=lam, mkmmd_loss_weight=0.1,
            beta_global_update_interval=2 if TINY else 20,
        )
        exchanger = FixedLayerExchanger(bases.TwinModel.exchange_global_model)
        extra_keys = tuple(logic.extra_loss_keys)
    elif algo == "ditto":
        base = engine.ClientLogic(model, engine.masked_cross_entropy)
        logic = make_it_personal(base, PersonalizedMode.DITTO, lam=lam)
        exchanger = FixedLayerExchanger(exchange_global_subtree)
        extra_keys = tuple(logic.extra_loss_keys)
    elif algo == "mr_mtl":
        base = engine.ClientLogic(model, engine.masked_cross_entropy)
        logic = make_it_personal(base, PersonalizedMode.MR_MTL, lam=lam)
        exchanger = KeepLocalExchanger()
        extra_keys = tuple(logic.extra_loss_keys)
    else:
        logic, exchanger = engine.ClientLogic(
            model, engine.masked_cross_entropy
        ), None
    return FederatedSimulation(
        logic=logic,
        tx=optax.sgd(lr),
        strategy=FedAvg(),
        datasets=DATASETS,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2 if TINY else 4,
        seed=seed,
        exchanger=exchanger,
        extra_loss_keys=extra_keys,
    )


grid = hp_grid(
    algo=["fedavg", "ditto", "mr_mtl"] if TINY
    else ["fedavg", "ditto", "mr_mtl", "ditto_mkmmd"],
    lr=[0.05] if TINY else [0.01, 0.05],
    lam=[0.1] if TINY else [0.01, 0.1, 1.0],
)
grid = [hp for hp in grid if hp["algo"] != "fedavg" or hp["lam"] == grid[0]["lam"]]

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1 if TINY else 3,
    score=lambda history: float(history[-1].eval_metrics["accuracy"]),
    minimize=False,
)
for r in results:
    print(json.dumps({"params": r.params,
                      "mean_accuracy": round(r.mean_score, 4)}))
best = results[0]
print(json.dumps({"best": best.params, "accuracy": round(best.mean_score, 4)}))
