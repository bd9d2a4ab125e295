"""A toy benchmark root for the CPU tests: a copy of ``benchmarks/`` with
tiny configurations and traffic mixes, one extra per-layer metric, one extra
end-to-end metric, one cell on the default ``auto`` path (the chunked scan)
and one cell on a second strategy (server momentum over the pseudo-gradient)
and a second client optimizer (SGD with momentum), each with its plain
reference. All of it comes in as NEW files plus BENCHMARK.json
entries: no file that exists is edited, which the extension test checks."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# set the way the real limits are: above the toy program's readings on the
# CPU (losses to 2e-3, worst leaves to 1.1e-2), below the float8 control's
TOY_LIMITS = {"loss_r1_gap": 0.02, "loss_r2_gap": 0.02, "loss_r3_gap": 0.02,
              "grad1_gap": 0.06, "dparam_gap": 0.06}

TOY_METRIC = '''"""Toy per-layer metric: rounds seen by the traced window."""


def read(ctx):
    return float(ctx["rounds"]) if ctx["rounds"] else None
'''


TOY_END_TO_END = '''"""Toy end-to-end metric: rounds completed a second."""


def read(ctx):
    return ctx["rounds"] / ctx["wall_s"]
'''

# a second strategy and a second client optimizer, program side and plain
TOY_FILES = {
    "strategies/toy_fedavgm.py": '''"""FedAvgM: server SGD with momentum over the pseudo-gradient."""


def build(strategy, job):
    import optax

    from fl4health_tpu.strategies.fedopt import FedOpt

    return FedOpt(optax.sgd(float(strategy["server_lr"]),
                            momentum=float(strategy["momentum"])))
''',
    "reference/strategies/toy_fedavgm.py": '''"""Plain FedAvgM: m <- beta * m + (x - mean); x <- x - lr * m."""

import functools
import os

import jax

from benchmarks.harness.spec import load_module

_fedavg = load_module("reference/strategies", "fedavg", os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run(*args, strategy, **kw):
    lr, beta = float(strategy["server_lr"]), float(strategy["momentum"])
    tmap = jax.tree_util.tree_map

    def init(params):
        return tmap(lambda a: a * 0.0, params)

    def update(params, mean, m):
        m = tmap(lambda mi, x, a: beta * mi + (x - a), m, params, mean)
        return tmap(lambda x, mi: x - lr * mi, params, m), m

    return _fedavg.run(*args, strategy=strategy, server=(init, update), **kw)
''',
    "optimizers/toy_momentum.py": '''def build_tx(opt):
    import optax

    return optax.sgd(float(opt["lr"]), momentum=float(opt["momentum"]))
''',
    "reference/optimizers/toy_momentum.py": '''"""Plain SGD with momentum: t <- beta * t + g; p <- p - lr * t."""

import jax


def init(params, opt):
    return jax.tree_util.tree_map(lambda a: a * 0.0, params)


def update(params, grads, trace, opt):
    lr, beta = float(opt["lr"]), float(opt["momentum"])
    trace = jax.tree_util.tree_map(lambda t, g: beta * t + g, trace, grads)
    return jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace), trace
''',
}
TOY_CHUNKED = {"execution_mode": "auto", "expect_mode": "chunked_scan",
               "check_calls": [3]}
TOY_SECOND_STRATEGY = {
    "strategy": {"name": "toy_fedavgm", "server_lr": 1.0, "momentum": 0.5},
    "optimizer": {"name": "toy_momentum", "lr": 0.005, "momentum": 0.5},
    "execution_mode": "pipelined", "expect_mode": "pipelined_per_round",
    "check_calls": [1, 2],
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def shrink_config(cfg: dict) -> dict:
    """Every width of a configuration cut to a toy size (tests only)."""
    return dict(cfg, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64, vocab_size=64,
                max_position_embeddings=32)


def shrink_job(job: dict) -> dict:
    small = dict(job, batch=4, local_steps=2, train_examples=[8, 12],
                 val_examples=4, rounds_per_fit=3)
    small["clients"] = 8 if job["clients"] > 4 else 4
    small["reference_client_block"] = min(
        int(job.get("reference_client_block", 1)), 4)
    if (job.get("data") or {}).get("kind") == "tokens":
        seq = 32 if (job.get("attention") or {}).get("kind") == "flash" else 16
        small["data"] = dict(job["data"], seq=seq)
        if job.get("max_positions"):
            small["max_positions"] = seq
        if (job.get("attention") or {}).get("kind") == "flash":
            small["attention"] = dict(job["attention"], block_q=16, block_k=16)
    return small


def make_root(tmp: str) -> tuple[str, dict]:
    """Copy the benchmark, add a toy twin of every real cell, the cell on the
    second strategy and the toy metrics. Returns (root, BENCHMARK dict)."""
    root = os.path.join(tmp, "root")
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "fixtures"))
    real = _load(os.path.join(REPO, "BENCHMARK.json"))
    bm = dict(real, configs=[], workloads=[])
    renamed = {}
    for c in real["configs"]:
        cfg = shrink_config(_load(os.path.join(REPO, c["file"])))
        name = "toy_" + c["name"]
        _dump(cfg, os.path.join(bench, "configs", name + ".json"))
        bm["configs"].append(dict(c, name=name,
                                  file=f"benchmarks/configs/{name}.json"))
    traffic_dir = os.path.join(REPO, "benchmarks", "traffic")
    for fname in sorted(os.listdir(traffic_dir)):
        job = shrink_job(_load(os.path.join(traffic_dir, fname)))
        _dump(job, os.path.join(bench, "traffic", "toy_" + fname))
    for w in real["workloads"]:
        name = f"toy_{w['config']}.toy_{w['traffic']}"
        renamed[w["name"]] = name
        bm["workloads"].append(dict(w, name=name, config="toy_" + w["config"],
                                    traffic="toy_" + w["traffic"]))
        _dump(TOY_LIMITS, os.path.join(bench, "limits", name + ".json"))
    for group in ("end_to_end", "per_layer"):
        bm[group] = [dict(m, workloads=[renamed[x] for x in m["workloads"]])
                     if "workloads" in m else dict(m) for m in real[group]]
    # cells that only new files make possible, on the first toy cell's job:
    # the default path (auto -> chunked scan), and a second strategy with a
    # second client optimizer
    first = bm["workloads"][0]
    base = _load(os.path.join(bench, "traffic", first["traffic"] + ".json"))
    for traffic, change in (("toy_chunked", TOY_CHUNKED),
                            ("toy_fedavgm", TOY_SECOND_STRATEGY)):
        _dump(dict(base, **change),
              os.path.join(bench, "traffic", traffic + ".json"))
        name = f"{first['config']}.{traffic}"
        bm["workloads"].append(dict(first, name=name, traffic=traffic))
        _dump(TOY_LIMITS, os.path.join(bench, "limits", name + ".json"))
    for rel, text in TOY_FILES.items():
        os.makedirs(os.path.dirname(os.path.join(bench, rel)), exist_ok=True)
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    with open(os.path.join(bench, "layer_metrics", "toy_rounds.py"), "w") as f:
        f.write(TOY_METRIC)
    with open(os.path.join(bench, "end_to_end", "toy_rounds_per_s.py"), "w") as f:
        f.write(TOY_END_TO_END)
    bm["end_to_end"].append({"name": "toy_rounds_per_s", "unit": "1/s",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock", "workloads": [name]})
    bm["per_layer"].append({"name": "toy_rounds", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "entry", "moves": "steps_per_s_chip"})
    _dump(bm, os.path.join(root, "BENCHMARK.json"))
    return root, bm


def fake_device(chips: int):
    from benchmarks.harness.device import DeviceInfo

    return DeviceInfo("cpu", "cpu", 8, chips, 1e12, 1e11)
