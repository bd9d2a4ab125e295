"""Resolve a workload name to its data files. No cell, configuration or
traffic name appears in harness code: all of it comes from BENCHMARK.json."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` by file path (a metric name may
    hold dots, so the import system's dotted names are not used)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    modname = "benchfile_" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up there
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def named(entry) -> dict:
    """A traffic file names a strategy, an optimizer or an objective by a
    bare name or by an object with ``name`` and its parameters."""
    return {"name": entry} if isinstance(entry, str) else dict(entry)


class Cell:
    """One workload of BENCHMARK.json with its configuration and traffic."""

    def __init__(self, workload: str, root: str = ROOT,
                 benchmark: dict | None = None):
        self.root = root
        self.benchmark = benchmark or load_json(
            os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in cells:
            raise KeyError(f"workload {workload!r} is not in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfgs = {c["name"]: c for c in self.benchmark["configs"]}
        entry = cfgs[self.workload["config"]]
        self.cfg = load_json(os.path.join(root, entry["file"]))
        # the benchmark's own directory: where the configuration file's
        # "configs" directory sits, with traffic/, limits/ and the modules
        # found by name beside it
        self.bench_dir = os.path.dirname(
            os.path.dirname(os.path.join(root, entry["file"])))
        self.job = load_json(os.path.join(
            self.bench_dir, "traffic", self.workload["traffic"] + ".json"))
        self.family = self.cfg["family"]
        # the one place the compute type is read from: the configuration
        self.compute_dtype = self.cfg["compute_dtype"]
        self.strategy = named(self.job["strategy"])
        self.optimizer = named(self.job["optimizer"])
        # what the clients minimise: their loss, its targets and their metric,
        # found by this name on both sides (objectives/,
        # reference/objectives/). A traffic file that names none gets the
        # one ``defaults.json`` names
        self.objective = named(self.job.get("objective") or load_json(
            os.path.join(self.bench_dir, "defaults.json"))["objective"])

    def metrics(self, group: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def limits(self) -> dict:
        path = os.path.join(self.bench_dir, "limits", self.name + ".json")
        return load_json(path)
