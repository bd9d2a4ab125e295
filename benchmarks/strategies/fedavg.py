"""Adapter: the program's ``FedAvg`` from the traffic file's ``strategy``
entry (no parameters: weighted by sample counts, full participation)."""

from __future__ import annotations


def build(strategy: dict, job: dict):
    from fl4health_tpu.strategies.fedavg import FedAvg

    return FedAvg()
