"""Capture a jax.profiler trace of compiled fit rounds on the live backend.

Usage: python tools/tpu_trace.py [timestamp-tag]

Runs a small (8-client) CIFAR-CNN FedAvg config — the bench headline shape,
shrunk so the trace stays readable — for 3 compiled rounds under
``jax.profiler.trace`` and prints ONE JSON line with the trace location and
sizes. One process (it holds the chip while it traces); SURVEY.md §5 names
profiling as a strictly-better-than-reference auxiliary (the reference has
none beyond wall-clock logging).
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    tag = sys.argv[1] if len(sys.argv) > 1 else "manual"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace_dir = os.path.join(repo, "artifacts", f"tpu_trace_{tag}")
    os.makedirs(trace_dir, exist_ok=True)

    os.environ.setdefault("FL4HEALTH_BENCH_CLIENTS", "8")
    os.environ.setdefault("FL4HEALTH_BENCH_ROUNDS", "3")
    sys.path.insert(0, repo)

    import jax
    import jax.numpy as jnp

    import bench

    platform = jax.devices()[0].platform
    _, sim = bench.make_sim("cifar_cnn")
    compiled, _ = bench.compile_fit_round(sim)
    mask = sim.client_manager.sample_all()
    val_batches, _ = sim._val_batches()
    r = jnp.asarray(1, jnp.int32)
    # warmup outside the trace so the trace shows steady-state rounds;
    # the executable DONATES the state args, so the warmup outputs (not the
    # consumed sim fields) seed the traced loop
    out = compiled(sim.server_state, sim.client_states, sim._round_batches(0),
                   mask, r, val_batches)
    jax.block_until_ready(out[0])

    with jax.profiler.trace(trace_dir):
        state, cstates = out[0], out[1]
        for i in range(3):
            state, cstates, losses, metrics, _pc = compiled(
                state, cstates, sim._round_batches(i + 1), mask, r, val_batches
            )
        jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])

    files = []
    total = 0
    for root, _dirs, names in os.walk(trace_dir):
        for n in names:
            p = os.path.join(root, n)
            sz = os.path.getsize(p)
            total += sz
            files.append({"file": os.path.relpath(p, repo), "bytes": sz})
    print(json.dumps({
        "ok": True,
        "platform": platform,
        "trace_dir": os.path.relpath(trace_dir, repo),
        "total_bytes": total,
        "n_files": len(files),
        "files": sorted(files, key=lambda f: -f["bytes"])[:10],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
