"""Personalization on the FedProx synthetic design (reference:
research/synthetic_data/ — fedavg vs ditto vs mr_mtl on the alpha/beta
heterogeneous synthetic corpus from the FedProx paper, hp-swept with
find_best_hp selection).

The reference preprocesses the corpus to disk (preprocess.py) and runs each
algorithm as its own slurm job; here the generator is
``datasets.synthetic.fedprox_synthetic`` (same W_k/v_k construction) and the
three algorithms share one sweep. alpha/beta control client heterogeneity —
the experiment's point is that personalized methods win as alpha/beta grow.

Run:  python research/synthetic_data/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/synthetic_data/sweep.py
Knobs: FL4HEALTH_SYNTH_ALPHA / FL4HEALTH_SYNTH_BETA (default 0.5/0.5).
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
from fl4health_tpu.datasets.synthetic import fedprox_synthetic
from fl4health_tpu.exchange.exchanger import FixedLayerExchanger
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_CLIENTS = 2 if TINY else 8
ROUNDS = 2 if TINY else 10
PER_CLIENT = 24 if TINY else 200
DIM, CLASSES = (12, 4) if TINY else (60, 10)
ALPHA = float(os.environ.get("FL4HEALTH_SYNTH_ALPHA", 0.5))
BETA = float(os.environ.get("FL4HEALTH_SYNTH_BETA", 0.5))


def client_datasets() -> list[ClientDataset]:
    shards = fedprox_synthetic(
        jax.random.PRNGKey(0), N_CLIENTS, PER_CLIENT,
        alpha=ALPHA, beta=BETA, dim=DIM, n_classes=CLASSES,
    )
    out = []
    for x, y in shards:
        x, y = np.asarray(x), np.asarray(y)
        cut = int(len(x) * 0.75)
        out.append(ClientDataset(x[:cut], y[:cut], x[cut:], y[cut:]))
    return out


DATASETS = client_datasets()


def build(seed: int, algo: str, lr: float, lam: float) -> FederatedSimulation:
    base = engine.ClientLogic(
        engine.from_flax(Mlp(features=(32,), n_outputs=CLASSES)),
        engine.masked_cross_entropy,
    )
    if algo == "ditto":
        logic = make_it_personal(base, PersonalizedMode.DITTO, lam=lam)
        exchanger = FixedLayerExchanger(exchange_global_subtree)
    elif algo == "mr_mtl":
        logic = make_it_personal(base, PersonalizedMode.MR_MTL, lam=lam)
        exchanger = KeepLocalExchanger()
    else:
        logic, exchanger = base, None
    return FederatedSimulation(
        logic=logic,
        tx=optax.sgd(lr),
        strategy=FedAvg(),
        datasets=DATASETS,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2 if TINY else 5,
        seed=seed,
        exchanger=exchanger,
    )


grid = hp_grid(
    algo=["fedavg", "ditto", "mr_mtl"],
    lr=[0.05] if TINY else [0.01, 0.05],
    lam=[0.1] if TINY else [0.01, 0.1, 1.0],
)
# lam is inert for fedavg — drop duplicate configs
grid = [hp for hp in grid if hp["algo"] != "fedavg" or hp["lam"] == grid[0]["lam"]]

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1 if TINY else 3,
    score=lambda history: float(history[-1].eval_metrics["accuracy"]),
    minimize=False,
)
print(json.dumps({"alpha": ALPHA, "beta": BETA}))
for r in results:
    print(json.dumps({"params": r.params,
                      "mean_accuracy": round(r.mean_score, 4)}))
best = results[0]
print(json.dumps({"best": best.params, "accuracy": round(best.mean_score, 4)}))
