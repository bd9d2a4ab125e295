#!/usr/bin/env python
"""Measured device time per spine stage, from a profiler capture.

The program names the seams of its aggregation spine with ``fl_stage::``
scopes (``observability/stages.py``). On a TPU the scope is not in an op
event's name (that is its HLO text) but in the event metadata's ``tf_op``
stat of the raw ``.xplane.pb``; the benchmark's reader
(``benchmarks/layer_metrics/stage_common.py`` over
``benchmarks/xplane_meta.py``) sums each op's self time under the innermost
scope of its name stack. This CLI prints that sum for a whole capture, most
time first: which stage a fused kernel could shorten, in milliseconds the
chip spent and not in a cost model's estimate. Ops under no scope (the
evaluation program, host transfers) read ``_unattributed``.

    python tools/roofline_report.py artifacts/obs/xprof
    python tools/roofline_report.py run.xplane.pb --json

The argument is the profile directory (``jax.profiler.start_trace``'s) or
the ``.xplane.pb`` itself. Exit codes: 0 ok, 2 capture missing, torn or not
an xplane.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import perf_report  # noqa: E402  (the shared table machinery)


def measured_stage_ms(path: str) -> dict[str, float]:
    """stage -> device self milliseconds over the whole capture at ``path``
    (a profile directory or an ``.xplane.pb``), by the benchmark's reader.
    Raises ``OSError``/``ValueError``/``RuntimeError`` on a capture that is
    missing, torn or not an xplane."""
    from benchmarks import trace_reduce
    from benchmarks.harness.spec import load_module

    stage = load_module("layer_metrics", "stage_common")
    xplane = (path if path.endswith(".xplane.pb")
              else trace_reduce.find_xplane(path))
    seconds = stage.by_stage(trace_reduce.load(xplane),
                             stage.read_tf_ops(xplane))
    return {k: v * 1e3 for k, v in seconds.items()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", metavar="PATH",
                    help="profile directory or .xplane.pb of a TPU capture")
    ap.add_argument("--json", action="store_true",
                    help="emit {stage: ms} as JSON instead of a table")
    args = ap.parse_args(argv)
    try:
        measured = measured_stage_ms(args.trace)
    except (OSError, ValueError, RuntimeError) as e:
        print(f"roofline_report: cannot read trace {args.trace}: {e}",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({"measured_ms": measured}, indent=2))
        return 0
    total = sum(measured.values())
    rows = [
        [stage, f"{ms:.3f}", f"{ms / total:.1%}" if total else "-"]
        for stage, ms in sorted(measured.items(), key=lambda kv: -kv[1])
    ]
    print(perf_report._render_generic_table(
        ("stage", "device_ms", "share"), rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
