"""Adapter: the program's ``FedAvg`` for a model whose base is held once.
The traffic file's ``strategy`` entry has no parameters; which leaves the
average runs over is the model's own predicate (``ModelDef.per_client``,
read by ``FederatedSimulation`` from the module the family adapter built),
not this file's: the simulation wraps the strategy it is given."""

from __future__ import annotations


def build(strategy: dict, job: dict):
    from fl4health_tpu.strategies.fedavg import FedAvg

    return FedAvg()
