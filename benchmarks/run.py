"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once on the machine it is started on and
prints the result object as the last line of standard output. Exits non-zero
and prints no result when JAX finds no TPU, too few chips, a device_kind that
``peaks.json`` does not know, or no program to measure.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import device, window
    from benchmarks.harness.spec import Cell

    cell = Cell(args.workload, root=ROOT)
    try:
        import fl4health_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark refused: no program to measure ({e})",
              file=sys.stderr)
        return 3
    window.configure_cache(ROOT)
    dev = device.gate(cell.bench_dir, cell.chips)
    result = window.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             dev, _T0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
