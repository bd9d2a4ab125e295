"""Build the system under test — ``FederatedSimulation`` with a default
``Observability()`` — from a configuration file and a traffic file alone."""

from __future__ import annotations

from . import datagen
from .spec import Cell, load_module


def nest(flat: dict) -> dict:
    """'a/b/c' paths -> nested dicts (the program's parameter tree)."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict) or hasattr(v, "items"):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def make_inputs(cell: Cell, seed: int):
    """(reference family module, w0 flat, (x_train, y_train, x_val, y_val),
    rows per client) — everything both the program and the reference start
    from, made from the seed."""
    ref = load_module("reference", cell.family, cell.bench_dir)
    w0 = datagen.make_weights(ref.param_spec(cell.cfg, cell.job), seed)
    objective = load_module("reference/objectives", cell.objective["name"],
                            cell.bench_dir)
    data = datagen.make_data(ref.input_spec(cell.cfg, cell.job), cell.job, seed,
                             objective.targets, cell.objective)
    return ref, w0, data, datagen.client_rows(cell.job)


def build_sim(cell: Cell, seed: int, w0: dict, data, rows, reporters=()):
    from fl4health_tpu.clients import engine
    from fl4health_tpu.observability import Observability
    from fl4health_tpu.server.simulation import (ClientDataset,
                                                 FederatedSimulation)

    job = cell.job
    module = load_module("families", cell.family, cell.bench_dir).build_module(
        cell.cfg, job)
    x_tr, y_tr, x_va, y_va = data
    datasets = [ClientDataset(x_tr[i, :n], y_tr[i, :n], x_va[i], y_va[i])
                for i, n in enumerate(rows)]
    # the strategy, the clients' optimizer and their objective (loss and
    # metric) are adapters found by the names in the traffic file, as the
    # model family is by the configuration's
    objective = load_module("objectives", cell.objective["name"],
                            cell.bench_dir)
    strategy = load_module("strategies", cell.strategy["name"],
                           cell.bench_dir).build(cell.strategy, job)
    tx = load_module("optimizers", cell.optimizer["name"],
                     cell.bench_dir).build_tx(cell.optimizer)
    mesh = None
    if job.get("mesh"):
        from fl4health_tpu.parallel.program import MeshConfig

        mesh = MeshConfig(**job["mesh"])
    sim = FederatedSimulation(
        logic=objective.build_logic(engine.from_flax(module), cell.cfg, job),
        tx=tx,
        strategy=strategy,
        datasets=datasets,
        batch_size=int(job["batch"]),
        metrics=objective.build_metrics(cell.cfg, job),
        local_steps=int(job["local_steps"]),
        seed=datagen.seed31(seed),
        execution_mode=job["execution_mode"],
        mesh=mesh,
        observability=Observability(),
        reporters=list(reporters),
    )
    # Under a mesh the default broadcast would stage the whole [clients, ...]
    # stack on one chip (16 x 436 MB for the encoder); the clients pull the
    # global weights at the start of every round anyway (FullExchanger), so
    # there the seeded weights are installed on the server side alone.
    sim.set_global_params(nest(w0), broadcast_to_clients=mesh is None)
    return sim
