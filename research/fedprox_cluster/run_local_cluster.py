"""FedProx cluster experiment, local-silo edition (reference:
research/fedprox_cluster/run_fl_cluster.sh — one slurm job per (mu, run):
a gRPC server + N client processes per job, logs scraped by
find_best_hp.py).

The TPU-native equivalent keeps the deployment shape: for every mu in the
grid, N LoopbackServer silos (one process-isolated handler each, talking
the transport codec's wire frames over TCP — the C++ framing when built)
run FedProx rounds against a coordinator, and every run drops a
JsonReporter-style dump under ``<sweep_dir>/mu_<mu>/Run<k>/``. Selection is
``find_best_hp_dir`` over the dump tree — the reference's file-based
find_best_hp flow, byte-for-byte in spirit.

Run:  python research/fedprox_cluster/run_local_cluster.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/fedprox_cluster/run_local_cluster.py
Output tree: FL4HEALTH_CLUSTER_DIR (default: ./cluster_runs under this dir).
"""

import json
import os
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fl4health_tpu.clients import engine
from fl4health_tpu.clients.fedprox import FedProxClientLogic
from fl4health_tpu.datasets.synthetic import fedprox_synthetic
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.transport import (
    LoopbackServer,
    broadcast_round,
    decode,
    encode,
    weighted_merge,
)
from fl4health_tpu.utils.hp_search import find_best_hp_dir

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_SILOS = 2 if TINY else 5
ROUNDS = 2 if TINY else 8
RUNS = 1 if TINY else 3
PER_SILO = 24 if TINY else 120
DIM, CLASSES = (8, 3) if TINY else (30, 6)
MUS = [0.1] if TINY else [0.01, 0.1, 1.0]
LOCAL_STEPS = 2 if TINY else 4


def make_silo(seed: int, mu: float, shard):
    """One 'hospital' process boundary: private shard + FedProx local
    training behind a TCP handler speaking wire frames."""
    x, y = np.asarray(shard[0]), np.asarray(shard[1])
    logic = FedProxClientLogic(
        engine.from_flax(Mlp(features=(16,), n_outputs=CLASSES)),
        engine.masked_cross_entropy,
    )
    tx = optax.sgd(0.05)
    state = engine.create_train_state(
        logic, tx, jax.random.PRNGKey(seed), jnp.asarray(x[:1])
    )
    train = jax.jit(
        engine.make_local_train(
            logic, tx, MetricManager((efficient.accuracy(),)),
            loss_keys=("backward", *logic.extra_loss_keys),
        )
    )

    def handler(frame: bytes) -> bytes:
        nonlocal state
        global_params = decode(frame, like=state.params)
        state = state.replace(params=global_params)
        # mu rides the payload in the reference protocol (the server packs
        # it); this cluster job pins it per-silo from the hp grid.
        ctx = logic.init_round_context(
            state, types.SimpleNamespace(
                drift_penalty_weight=jnp.asarray(mu, jnp.float32)
            )
        )
        batches = engine.epoch_batches(
            state.rng, jnp.asarray(x), jnp.asarray(y), 8,
            n_steps=LOCAL_STEPS,
        )
        new_state, losses, metrics, _ = train(state, ctx, batches)
        state = new_state
        return encode({
            "params": state.params,
            "n": jnp.asarray(float(len(x))),
            "loss": losses["backward"],
            "accuracy": metrics["accuracy"],
        })

    return LoopbackServer(handler), state.params


def run_job(mu: float, run_idx: int, out_dir: Path) -> None:
    """One (mu, run) cluster job: silos up, FedProx rounds over the wire,
    JsonReporter-style dump down."""
    shards = fedprox_synthetic(
        jax.random.PRNGKey(run_idx), N_SILOS, PER_SILO,
        alpha=0.5, beta=0.5, dim=DIM, n_classes=CLASSES,
    )
    silos = [make_silo(100 * run_idx + i, mu, s)
             for i, s in enumerate(shards)]
    init_params = silos[0][1]
    template = {"params": init_params, "n": jnp.zeros(()),
                "loss": jnp.zeros(()), "accuracy": jnp.zeros(())}
    global_params = init_params
    dump: dict = {"host_type": "server", "mu": mu, "rounds": {}}
    try:
        for rnd in range(1, ROUNDS + 1):
            replies = broadcast_round(
                [(srv.host, srv.port) for srv, _ in silos],
                global_params, template,
            )
            global_params, _ = weighted_merge(replies)
            dump["rounds"][str(rnd)] = {
                "fit_loss": float(np.mean([float(r["loss"]) for r in replies])),
                "accuracy": float(np.mean([float(r["accuracy"]) for r in replies])),
            }
    finally:
        for srv, _ in silos:
            srv.close()
    run_dir = out_dir / f"mu_{mu}" / f"Run{run_idx + 1}"
    run_dir.mkdir(parents=True, exist_ok=True)
    (run_dir / "server_metrics.json").write_text(json.dumps(dump, indent=2))


def main() -> None:
    root = Path(os.environ.get(
        "FL4HEALTH_CLUSTER_DIR", Path(__file__).parent / "cluster_runs"
    ))
    # Each invocation gets a fresh sweep subtree: find_best_hp_dir scans
    # every hp folder under the dir it's given, so stale mu_* trees from a
    # previous (possibly differently-configured) invocation must not enter
    # this run's selection.
    import tempfile

    root.mkdir(parents=True, exist_ok=True)
    out_dir = Path(tempfile.mkdtemp(prefix="sweep_", dir=root))
    print(json.dumps({"sweep_dir": str(out_dir)}))
    for mu in MUS:
        for run_idx in range(RUNS):
            run_job(mu, run_idx, out_dir)
            print(json.dumps({"job": f"mu_{mu}", "run": run_idx + 1,
                              "status": "done"}))
    # find_best_hp_dir resolves the dotted metric inside the LAST round's
    # record of each dump — the reference's log-scrape selection.
    best_dir, best_score = find_best_hp_dir(
        out_dir, metric="accuracy", minimize=False,
    )
    print(json.dumps({
        "best": best_dir.name if best_dir else None,
        "mean_final_accuracy":
            round(best_score, 4) if best_score is not None else None,
    }))


if __name__ == "__main__":
    main()
