"""Shared plumbing for the examples corpus.

Role of the reference's per-example boilerplate
(/root/reference/examples/*/server.py + client.py + config.yaml, SURVEY
Appendix A): each example here is ONE ``run.py`` (the cohort is a single
SPMD program — there is no server/client process split to script) plus the
same-shaped ``config.yaml``. This module carries the shared pieces: config
loading, dataset construction (real MNIST from disk when present, else the
deterministic synthetic corpus — explicitly, never silently), model
builders, and the run/report loop.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import jax
import numpy as np

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from fl4health_tpu.utils.runtime import configure_compile_cache  # noqa: E402

configure_compile_cache()

from fl4health_tpu.clients import engine  # noqa: E402
from fl4health_tpu.datasets.partitioners import DirichletLabelBasedAllocation  # noqa: E402
from fl4health_tpu.datasets.synthetic import synthetic_classification  # noqa: E402
from fl4health_tpu.datasets.vision import federated_client_datasets  # noqa: E402
from fl4health_tpu.metrics import efficient  # noqa: E402
from fl4health_tpu.metrics.base import MetricManager  # noqa: E402
from fl4health_tpu.models.cnn import MnistNet, Mlp  # noqa: E402
from fl4health_tpu.utils.config import load_config  # noqa: E402

MNIST_DATA_DIR = Path(os.environ.get("FL4HEALTH_MNIST_DIR", "/root/data/mnist"))


def example_config(example_dir: str | Path) -> dict:
    """Load the example's config.yaml with env overrides for smoke tests
    (FL4HEALTH_EXAMPLE_ROUNDS / _CLIENTS shrink any example)."""
    cfg = load_config(str(Path(example_dir) / "config.yaml"))
    if os.environ.get("FL4HEALTH_EXAMPLE_ROUNDS"):
        cfg["n_server_rounds"] = int(os.environ["FL4HEALTH_EXAMPLE_ROUNDS"])
    if os.environ.get("FL4HEALTH_EXAMPLE_CLIENTS"):
        cfg["n_clients"] = int(os.environ["FL4HEALTH_EXAMPLE_CLIENTS"])
    return cfg


def mnist_client_datasets(cfg: dict, image_hw: int = 14):
    """Dirichlet-partitioned MNIST-shaped client datasets. Real MNIST is used
    when present on disk; otherwise the seeded synthetic corpus (stated on
    stdout so runs are never silently synthetic)."""
    n_clients = int(cfg.get("n_clients", 4))
    if os.environ.get("FL4HEALTH_EXAMPLE_TINY"):
        # smoke-test mode: quarter-size synthetic data, fastest compile
        x, y = synthetic_classification(
            jax.random.PRNGKey(0), 240, (8, 8, 1), 10, class_sep=1.5
        )
        x, y = np.asarray(x), np.asarray(y)
        print("# data: tiny synthetic corpus (FL4HEALTH_EXAMPLE_TINY)")
        # near-uniform allocation: 240 samples over 10 labels can't honor
        # min_label_examples under a skewed draw at 4+ partitions
        partitioner = DirichletLabelBasedAllocation(
            number_of_partitions=n_clients, unique_labels=list(range(10)),
            beta=5.0, min_label_examples=1, hash_key=42,
        )
        return federated_client_datasets(
            x, y, n_clients=n_clients, partitioner=partitioner, hash_key=7
        )
    try:
        from fl4health_tpu.datasets.vision import load_mnist_arrays

        # load_mnist_arrays already returns [N,28,28,1] float32 normalized
        x, y = load_mnist_arrays(MNIST_DATA_DIR, train=True)
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.int64)
        idx = np.random.default_rng(0).permutation(len(x))[:2048]
        x, y = x[idx], y[idx]
        print(f"# data: real MNIST from {MNIST_DATA_DIR}")
    except (FileNotFoundError, OSError):
        x, y = synthetic_classification(
            jax.random.PRNGKey(0), 960, (image_hw, image_hw, 1), 10, class_sep=1.2
        )
        x, y = np.asarray(x), np.asarray(y)
        print("# data: synthetic MNIST-shaped corpus (no real MNIST on disk)")
    partitioner = DirichletLabelBasedAllocation(
        number_of_partitions=n_clients, unique_labels=list(range(10)),
        beta=float(cfg.get("dirichlet_beta", 0.8)), min_label_examples=1,
        hash_key=42,
    )
    return federated_client_datasets(
        x, y, n_clients=n_clients, partitioner=partitioner, hash_key=7
    )


def mnist_model(cfg: dict):
    return engine.from_flax(MnistNet(hidden=int(cfg.get("hidden", 32))))


def mlp_model(cfg: dict, n_outputs: int = 10):
    return engine.from_flax(
        Mlp(features=(int(cfg.get("hidden", 32)),), n_outputs=n_outputs)
    )


def accuracy_metrics() -> MetricManager:
    return MetricManager((efficient.accuracy(),))


def run_and_report(sim_or_server, cfg: dict, **fit_kwargs):
    """fit + per-round JSON lines on stdout (the JsonReporter role the
    reference smoke tests scrape, reporting/base.py is the in-library path)."""
    n_rounds = int(cfg.get("n_server_rounds", 3))
    history = sim_or_server.fit(n_rounds, **fit_kwargs)
    if isinstance(history, tuple):  # DP servers return (history, epsilon)
        history, epsilon = history
        print(json.dumps({"epsilon": round(float(epsilon), 4)}))

    def headline_metric(rec) -> tuple[str, float]:
        # accuracy when present; otherwise the config's own lead metric
        # (e.g. seg_dice for the nnU-Net example); metric-less SSL configs
        # report their eval loss
        metrics = rec.eval_metrics
        if "accuracy" in metrics:
            return "accuracy", metrics["accuracy"]
        if metrics:
            key = sorted(metrics)[0]
            return key, metrics[key]
        return "loss", rec.eval_losses.get("checkpoint", float("nan"))

    for rec in history:
        name, value = headline_metric(rec)
        print(
            json.dumps(
                {
                    "round": rec.round,
                    "fit_loss": round(rec.fit_losses.get("backward", float("nan")), 5),
                    "eval_loss": round(rec.eval_losses.get("checkpoint", float("nan")), 5),
                    f"eval_{name}": round(value, 5),
                }
            )
        )
    name, value = headline_metric(history[-1])
    print(
        json.dumps(
            {"final": True, "rounds": len(history), f"eval_{name}": round(value, 5)}
        )
    )
    return history
