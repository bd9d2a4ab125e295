"""Readings from which a cell's limits are set (benchmarks/limits/<cell>.json).

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--control float8_operands] [--out file.jsonl]

For each seed of ``--seeds`` it drives the program through the cell's check
calls (no measured window: training's readings need none) and compares with
the float32 reference. For each seed of ``--control-seeds`` it puts the
reference computed one precision step below the configuration's stated
compute type in the program's place. Every reading goes through the
comparison the benchmark's runs use (``check.decide`` with the cell's limits
file): one JSON line per reading with its ``correct``, and the exit code is 1
if a sound run comes out not correct or a control comes out correct. With
``--no-limits`` nothing is judged (a new cell has no limits yet). Needs the
chip the cell asks for; not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-limits", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import check, device, window
    from benchmarks.harness.spec import Cell, load_module

    cell = Cell(args.workload, root=ROOT)
    window.configure_cache(ROOT)
    device.gate(cell.bench_dir, cell.chips)
    num = load_module("reference", "numerics", cell.bench_dir)
    control = args.control or num.NEXT_BELOW[cell.compute_dtype]
    limits = None if args.no_limits else cell.limits()
    out = open(args.out, "a") if args.out else None
    wrong = []

    def emit(rec, want_correct):
        if limits is not None:
            rec["correct"], rec["checks"] = check.decide(rec["numbers"], limits)
            if rec["correct"] != want_correct:
                wrong.append((rec["who"], rec["seed"]))
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    ints = lambda s: [int(v) for v in s.split(",") if v]  # noqa: E731
    refs = {}
    for seed in ints(args.seeds):
        sim, prog = window.first_rounds(cell, seed)
        window.release(sim)
        del sim
        refs[seed] = window.reference_rounds(cell, seed)
        emit({"workload": cell.name, "seed": seed, "who": "program",
              "numbers": check.numbers(prog, refs[seed]),
              "losses": prog["losses"], "ref_losses": refs[seed]["losses"]},
             want_correct=True)
    for seed in ints(args.control_seeds):
        ref = refs.get(seed) or window.reference_rounds(cell, seed)
        low = window.reference_rounds(cell, seed, numerics=control)
        emit({"workload": cell.name, "seed": seed, "who": control,
              "numbers": check.numbers(low, ref), "losses": low["losses"],
              "ref_losses": ref["losses"]}, want_correct=False)
    for who, seed in wrong:
        print(f"calibrate: {who} seed {seed} came out "
              f"{'correct' if who != 'program' else 'not correct'}",
              file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
