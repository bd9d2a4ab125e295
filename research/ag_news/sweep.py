"""AG-News-class experiments: partial weight exchange on a BERT-shaped
transformer (reference: research/ag_news/dynamic_layer_exchange/ +
research/ag_news/sparse_tensor_exchange/ — BERT fine-tuning under
DynamicLayerExchanger / sparse top-score exchange, hp-swept over exchange
budgets; selection semantics from research/*/find_best_hp.py).

The reference runs these on real AG-News through HF BERT; this harness runs
the same experiment shape — drift-ranked dynamic layer exchange vs sparse
COO exchange vs full exchange, swept over exchange budgets — on the
TPU-native transformer. Real AG-News token ids can be dropped in via
FL4HEALTH_AGNEWS_NPZ (x: [N, T] int32 ids, y: [N] labels); without it the
corpus is synthetic (zero-egress box).

Run:  python research/ag_news/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/ag_news/sweep.py
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
from fl4health_tpu.datasets.synthetic import synthetic_text_classification
from fl4health_tpu.exchange.exchanger import (
    DynamicLayerExchanger,
    SparseExchanger,
)
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.transformer import TransformerClassifier
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.dynamic_layer import (
    FedAvgDynamicLayer,
    FedAvgSparse,
)
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_CLIENTS = 2 if TINY else 4
ROUNDS = 2 if TINY else 8
N_CLASSES = 4  # AG-News: World / Sports / Business / Sci-Tech
VOCAB = 64 if TINY else 512
SEQ = 8 if TINY else 64
PER_CLIENT = 24 if TINY else 256


def client_datasets() -> list[ClientDataset]:
    npz = os.environ.get("FL4HEALTH_AGNEWS_NPZ")
    if npz and Path(npz).exists():
        with np.load(npz) as z:
            x, y = z["x"].astype(np.int32), z["y"].astype(np.int32)
        print("# data: real AG-News token ids from", npz)
        rng = np.random.default_rng(0)
        idx = rng.permutation(len(x))
        shards = np.array_split(idx[: N_CLIENTS * PER_CLIENT], N_CLIENTS)
        out = []
        for sh in shards:
            cut = int(len(sh) * 0.75)
            out.append(ClientDataset(x[sh[:cut]], y[sh[:cut]],
                                     x[sh[cut:]], y[sh[cut:]]))
        return out
    print("# data: synthetic AG-News-shaped token corpus")
    out = []
    for i in range(N_CLIENTS):
        x, y = synthetic_text_classification(
            jax.random.PRNGKey(50 + i), PER_CLIENT, VOCAB, SEQ, N_CLASSES,
            class_sep=2.5,
        )
        cut = int(PER_CLIENT * 0.75)
        out.append(ClientDataset(x[:cut], y[:cut], x[cut:], y[cut:]))
    return out


DATASETS = client_datasets()


def build(seed: int, exchange: str, budget: float,
          lr: float) -> FederatedSimulation:
    model = engine.from_flax(TransformerClassifier(
        vocab_size=VOCAB, n_classes=N_CLASSES,
        d_model=16 if TINY else 64, n_heads=2, n_layers=1 if TINY else 2,
        d_ff=32 if TINY else 128, max_len=SEQ,
    ))
    if exchange == "dynamic_layer":
        strategy, exchanger = FedAvgDynamicLayer(), DynamicLayerExchanger(
            mode="topk", exchange_fraction=budget
        )
    elif exchange == "sparse_coo":
        strategy, exchanger = FedAvgSparse(), SparseExchanger(
            sparsity_level=budget
        )
    else:
        strategy, exchanger = FedAvg(), None
    return FederatedSimulation(
        logic=engine.ClientLogic(model, engine.masked_cross_entropy),
        tx=optax.adam(lr),
        strategy=strategy,
        datasets=DATASETS,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2 if TINY else 4,
        seed=seed,
        exchanger=exchanger,
    )


grid = hp_grid(
    exchange=["full", "dynamic_layer", "sparse_coo"],
    budget=[0.5] if TINY else [0.1, 0.25, 0.5],
    lr=[1e-3] if TINY else [5e-4, 1e-3],
)
# budget is inert for full exchange — drop duplicate configs
grid = [hp for hp in grid
        if hp["exchange"] != "full" or hp["budget"] == grid[0]["budget"]]

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
