"""The comparison that decides ``correct``.

What is compared is what ``fit()`` itself produced in the rounds the
reference can follow: set-up builds one simulation, drives it from the seed
through the traffic file's ``check_calls`` with ``fit()`` (the per-round
driver's round programs are the window's own; the chunked cell's check call
is the window's own call shape) and hands that same object to the measured
window. Compared are each of those rounds' aggregated fit loss as ``fit()``
returned it, and the per-leaf norms of the global weights' change, read from
the simulation's own state after the first check call (the first aggregated
pseudo-gradient as the server gets it) and after the last. The window's own
later rounds are not compared (the reference would have to follow them all):
of those only finite losses and zero compiles are checked.

Per-leaf numbers go by the worst leaf: the gap between the program's norm
and the reference's (not the norm of their difference), measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger, since some leaves hardly move.
"""

from __future__ import annotations

import math
import statistics

HUGE = 1e30  # stands for "no finite number" in a JSON line


def worst_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    if set(prog) != set(ref):
        return HUGE, "leaf-set-mismatch"
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            return HUGE, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def numbers(prog: dict, ref: dict, n_losses: int = 3) -> dict:
    """name -> value for every number compared. ``prog`` and ``ref`` are
    {"losses": [...], "snapshots": [{leaf: norm}, ...]}."""
    out = {}
    k = min(n_losses, len(ref["losses"]), len(prog["losses"]))
    for i in range(k):
        p, r = prog["losses"][i], ref["losses"][i]
        gap = abs(p - r) / max(abs(r), 1e-30)
        out[f"loss_r{i + 1}_gap"] = gap if math.isfinite(gap) else HUGE
    out["grad1_gap"], _ = worst_leaf_gap(prog["snapshots"][0],
                                         ref["snapshots"][0])
    if len(ref["snapshots"]) > 1:
        out["dparam_gap"], _ = worst_leaf_gap(prog["snapshots"][-1],
                                              ref["snapshots"][-1])
    return out


def decide(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). A number with no limit in the
    cell's limits file is a failure: nothing is compared against a guess."""
    checks, ok = {}, True
    for name, v in values.items():
        limit = limits.get(name)
        checks[name] = {"value": v, "limit": limit}
        if limit is None or not (v <= limit):
            ok = False
    return ok, checks
