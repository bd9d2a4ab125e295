"""HP sweep: FedAvg vs FedProx on CIFAR-shaped non-IID clients (reference:
research/cifar10/ + research/*/find_best_hp.py selection semantics).

Run:  python research/cifar10/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/cifar10/sweep.py
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
from fl4health_tpu.clients.fedprox import FedProxClientLogic
from fl4health_tpu.datasets.partitioners import DirichletLabelBasedAllocation
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.datasets.vision import federated_client_datasets
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import CifarNet
from fl4health_tpu.server.simulation import FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.strategies.fedprox import FedAvgWithAdaptiveConstraint
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_CLIENTS = 2 if TINY else 8
ROUNDS = 2 if TINY else 10
HW = 8 if TINY else 32


def client_datasets():
    try:
        from fl4health_tpu.datasets.vision import load_cifar10_arrays

        x, y = load_cifar10_arrays(
            Path(os.environ.get("FL4HEALTH_CIFAR_DIR", "/root/data/cifar10")),
            train=True,
        )
        idx = np.random.default_rng(0).permutation(len(x))[: 4096 if not TINY else 256]
        x, y = np.asarray(x, np.float32)[idx], np.asarray(y, np.int64)[idx]
        print("# data: real CIFAR-10")
    except (FileNotFoundError, OSError):
        x, y = synthetic_classification(
            jax.random.PRNGKey(0), 256 if TINY else 2048, (HW, HW, 3), 10,
            class_sep=1.5,
        )
        x, y = np.asarray(x), np.asarray(y)
        print("# data: synthetic CIFAR-shaped corpus")
    part = DirichletLabelBasedAllocation(
        number_of_partitions=N_CLIENTS, unique_labels=list(range(10)),
        beta=0.5, min_label_examples=1, hash_key=13,
    )
    return federated_client_datasets(x, y, n_clients=N_CLIENTS,
                                     partitioner=part, hash_key=5)


DATASETS = client_datasets()


def build(seed: int, algo: str, lr: float, mu: float) -> FederatedSimulation:
    model = engine.from_flax(CifarNet())
    if algo == "fedavg":
        logic = engine.ClientLogic(model, engine.masked_cross_entropy)
        strategy = FedAvg()
    else:
        logic = FedProxClientLogic(model, engine.masked_cross_entropy)
        strategy = FedAvgWithAdaptiveConstraint(
            initial_drift_penalty_weight=mu, adapt_loss_weight=False
        )
    return FederatedSimulation(
        logic=logic,
        tx=optax.sgd(lr),
        strategy=strategy,
        datasets=DATASETS,
        batch_size=16,
        metrics=MetricManager((efficient.accuracy(),)),
        local_epochs=1,
        seed=seed,
    )


grid = hp_grid(
    algo=["fedavg", "fedprox"],
    lr=[0.05] if TINY else [0.01, 0.05, 0.1],
    mu=[0.1] if TINY else [0.01, 0.1, 1.0],
)
# mu is inert for fedavg — drop duplicate configs
grid = [hp for hp in grid if hp["algo"] != "fedavg" or hp["mu"] == grid[0]["mu"]]

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
