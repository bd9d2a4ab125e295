"""Measure the dense-vs-flash attention crossover on real TPU.

Runs the bench's BERT-class transformer child at several sequence lengths,
once with the dense XLA attention core and once with the Pallas flash
kernel, and writes CROSSOVER_tpu_<ts>.json. Answers, with silicon evidence,
where `attention_fn=flash_attention` should become the default for
`TransformerClassifier` (today: dense at seq 128 per the bench config,
flash only in the long-context config). Both arms run with
FL4HEALTH_BENCH_ANALYTIC_FLOPS=1, so every cell's tflops/mfu_pct uses the
same analytic 3x-forward numerator and the columns compare directly.

Usage (needs the TPU; each cell is one sequential ``bench.py`` child and
costs one BERT compile, so the sweep is budgeted per child. This parent
never initialises a JAX backend — the chip belongs to the child):

    python tools/flash_crossover.py            # seqs 128,512 both arms
    FL4HEALTH_CROSSOVER_SEQS=128,512,1024 python tools/flash_crossover.py

No reference counterpart (the reference delegates attention to torch);
this is TPU-native perf methodology like tools/a100_band_anchor.py.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from fl4health_tpu.utils.runtime import last_json_line  # noqa: E402

CHILD_TIMEOUT_S = int(os.environ.get("FL4HEALTH_CROSSOVER_CHILD_S", 1500))


def run_cell(seq: int, flash: bool) -> dict:
    env = dict(os.environ)
    env.update({
        "FL4HEALTH_BENCH_CHILD": "1",
        "FL4HEALTH_BENCH_ONLY": "transformer",
        "FL4HEALTH_BENCH_SEQ": str(seq),
        "FL4HEALTH_BENCH_FLASH": "1" if flash else "0",
        # One analytic FLOP numerator for BOTH arms: the flash arm must use
        # it (cost_analysis cannot see Pallas custom-call FLOPs) and the
        # dense arm's cost-model figure counts extra non-matmul ops, so a
        # mixed-numerator sweep would compare incomparable mfu_pct columns.
        "FL4HEALTH_BENCH_ANALYTIC_FLOPS": "1",
    })
    try:
        res = subprocess.run(
            [sys.executable, "bench.py"], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=CHILD_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"timed out ({CHILD_TIMEOUT_S}s)"}
    rec = last_json_line(res.stdout)
    if rec is None:
        return {"error": f"rc={res.returncode}", "stderr_tail": res.stderr[-1500:]}
    return rec


def main() -> int:
    seqs = [int(s) for s in os.environ.get(
        "FL4HEALTH_CROSSOVER_SEQS", "128,512").split(",")]
    ts = datetime.datetime.now(datetime.timezone.utc).strftime("%Y%m%d_%H%M%S")
    out = {"seqs": seqs, "cells": []}
    for seq in seqs:
        for flash in (False, True):
            rec = run_cell(seq, flash)
            cell = {"seq": seq, "attention": "pallas_flash" if flash else "dense",
                    "steps_per_sec": rec.get("steps_per_sec_per_chip"),
                    "tflops": rec.get("tflops"), "mfu_pct": rec.get("mfu_pct"),
                    "flops_source": rec.get("flops_source")}
            if "error" in rec:
                cell["error"] = rec["error"]
            out["cells"].append(cell)
            print(json.dumps(cell), flush=True)
    # decide per-seq winner on steps/s (same model/config both arms)
    winners = {}
    for seq in seqs:
        pair = {c["attention"]: c.get("steps_per_sec") or 0.0
                for c in out["cells"] if c["seq"] == seq}
        if pair.get("dense") or pair.get("pallas_flash"):
            winners[str(seq)] = max(pair, key=lambda k: pair[k])
    out["winner_by_seq"] = winners
    path = os.path.join(REPO, f"CROSSOVER_tpu_{ts}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
