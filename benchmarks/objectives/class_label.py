"""Adapter: the class-label objective as the program takes it. An
objective's program side is two functions, found by the name in the traffic
file's ``objective`` entry: ``build_logic(model_def, cfg, job)`` gives the
clients' ``engine.ClientLogic`` (their training and evaluation loss over the
batches ``reference/objectives/<name>.targets`` drew) and
``build_metrics(cfg, job)`` the ``MetricManager`` they report with."""

from __future__ import annotations


def build_logic(model_def, cfg: dict, job: dict):
    from fl4health_tpu.clients import engine

    return engine.ClientLogic(model_def, engine.masked_cross_entropy)


def build_metrics(cfg: dict, job: dict):
    from fl4health_tpu.metrics import efficient
    from fl4health_tpu.metrics.base import MetricManager

    return MetricManager((efficient.accuracy(),))
