"""Federated nnU-Net prostate-segmentation harness (reference:
research/picai/ — nnU-Net under FedAvg on the PI-CAI bpMRI volumes, plus a
central/single-node baseline; monai/nnunet_scripts drive the real data).

The real PI-CAI corpus cannot exist on this box (zero egress); the harness
keeps the experiment SHAPE — plans negotiation from client fingerprints,
deep-supervised U-Net from the plans, on-device augmentation, polyLR SGD,
dice selection over an lr sweep, and a "central" (single-client) baseline
arm mirroring research/picai/central. Drop real volumes in via
FL4HEALTH_PICAI_DIR (per-client .npz files with `volume` [D,H,W,C] and
`segmentation` [D,H,W] arrays) and the same sweep runs on them.

Run:  python research/picai/sweep.py
Tiny: FL4HEALTH_SWEEP_TINY=1 python research/picai/sweep.py
"""

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))

import jax
import numpy as np

from fl4health_tpu.clients import engine
from fl4health_tpu.clients.nnunet import (
    NnunetClientLogic,
    make_nnunet_properties_provider,
)
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.metrics.efficient import segmentation_dice
from fl4health_tpu.models.unet import deep_supervision_strides, unet_from_plans
from fl4health_tpu.nnunet import extract_patch_dataset, nnunet_optimizer
from fl4health_tpu.server.nnunet import NnunetServer
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg
from fl4health_tpu.utils.hp_search import hp_grid, sweep

TINY = bool(os.environ.get("FL4HEALTH_SWEEP_TINY"))
N_CLIENTS = 2 if TINY else 3
ROUNDS = 2 if TINY else 8
SIZE = 10 if TINY else 24
N_VOLUMES = 2 if TINY else 6
N_PATCHES = 8 if TINY else 40
LOCAL_STEPS = 2 if TINY else 4


def _synth_prostate(seed: int, n: int, size: int):
    """Ellipsoid-lesion phantoms: background noise + a bright lesion —
    enough anisotropy/label sparsity to exercise the nnU-Net paths."""
    rng = np.random.default_rng(seed)
    vols, segs = [], []
    for _ in range(n):
        coords = np.stack(
            np.meshgrid(*[np.arange(size)] * 3, indexing="ij"), -1
        ).astype(float)
        c = np.asarray([rng.uniform(size * 0.3, size * 0.7) for _ in range(3)])
        radii = np.asarray([size * rng.uniform(0.12, 0.3) for _ in range(3)])
        seg = (np.sum(((coords - c) / radii) ** 2, -1) < 1.0).astype(np.int32)
        vols.append(
            (rng.normal(0, 0.35, (size,) * 3)[..., None]
             + 1.2 * seg[..., None]).astype(np.float32)
        )
        segs.append(seg)
    return vols, segs


def _load_clients():
    data_dir = os.environ.get("FL4HEALTH_PICAI_DIR")
    if data_dir and Path(data_dir).exists():
        clients = []
        for cdir in sorted(Path(data_dir).iterdir()):
            if not cdir.is_dir():
                continue
            vols, segs = [], []
            for f in sorted(cdir.glob("*.npz")):
                with np.load(f) as z:
                    vols.append(np.asarray(z["volume"], np.float32))
                    segs.append(np.asarray(z["segmentation"], np.int32))
            if vols:
                clients.append((vols, segs))
        if clients:
            print(f"# data: real volumes from {data_dir} "
                  f"({len(clients)} clients)")
            return clients
    print("# data: synthetic prostate phantoms")
    return [_synth_prostate(7 * (i + 1), N_VOLUMES, SIZE)
            for i in range(N_CLIENTS)]


CLIENT_DATA = _load_clients()


def build(seed: int, lr: float, central: bool) -> "NnunetServer":
    data = ([(sum((v for v, _ in CLIENT_DATA), []),
              sum((s for _, s in CLIENT_DATA), []))]
            if central else CLIENT_DATA)
    providers = [
        make_nnunet_properties_provider(
            v, [(1.0, 1.0, 1.0)] * len(v), s, max_patch_voxels=SIZE ** 3
        )
        for v, s in data
    ]

    def sim_builder(plans, n_in, n_heads):
        cfg_ = plans["configurations"]["3d_fullres"]
        cfg_["features_per_stage"] = [
            max(f // 4, 8) for f in cfg_["features_per_stage"]
        ]
        net = unet_from_plans(plans, n_in, n_heads)
        logic = NnunetClientLogic(
            engine.from_flax(net), ds_strides=deep_supervision_strides(plans)
        )
        datasets = []
        for i, (v, s) in enumerate(data):
            x, y = extract_patch_dataset(v, s, plans, n_patches=N_PATCHES,
                                         seed=seed * 101 + i)
            cut = int(N_PATCHES * 0.75)
            datasets.append(
                ClientDataset(x[:cut], y[:cut], x[cut:], y[cut:])
            )
        return FederatedSimulation(
            logic=logic,
            tx=nnunet_optimizer(lr, ROUNDS * LOCAL_STEPS),
            strategy=FedAvg(),
            datasets=datasets,
            batch_size=2,
            metrics=MetricManager((segmentation_dice(n_heads),)),
            local_steps=LOCAL_STEPS,
            seed=seed,
            extra_loss_keys=("dice", "ce"),
        )

    return NnunetServer(
        config={"n_server_rounds": ROUNDS},
        property_providers=providers,
        sim_builder=sim_builder,
    )


grid = hp_grid(
    lr=[5e-3] if TINY else [1e-3, 5e-3, 1e-2],
    central=[False] if TINY else [False, True],
)

results = sweep(
    build, grid, n_rounds=ROUNDS, n_seeds=1,
    score=lambda history: float(history[-1].eval_metrics["seg_dice"]),
    minimize=False,
)
for r in results:
    print(json.dumps({"params": r.params,
                      "mean_dice": round(r.mean_score, 4)}))
best = results[0]
print(json.dumps({"best": best.params, "dice": round(best.mean_score, 4)}))
