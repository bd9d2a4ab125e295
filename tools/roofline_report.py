#!/usr/bin/env python
"""Measured device time per spine stage, and the client step's by part and
pass, from a profiler capture.

The program names the stages of a round with ``fl_stage::`` scopes and the
parts of the client step with ``fl_layer::`` scopes
(``observability/stages.py``). On a TPU a scope is not in an op event's name
(that is its HLO text) but in the event metadata's ``tf_op`` stat of the
raw ``.xplane.pb``; the benchmark's readers
(``benchmarks/layer_metrics/stage_common.py`` and ``pass_common.py`` over
``benchmarks/xplane_meta.py``) sum each op's self time under the innermost
stage of its name stack. This CLI prints that sum for a whole capture, most
time first: which stage a fused kernel could shorten, in milliseconds the
chip spent and not in a cost model's estimate. Ops under no stage (the
shared base's cast, host transfers) read ``_unattributed``. Under it, the
table of ``local_train`` by part (every ``fl_layer::`` of an op's name
stack: nested parts each count it) and pass (``forward`` / ``recompute`` /
``backward`` / ``update``, from JAX's own markers), with the evaluation
program's forwards by part beside it; ``_unscoped`` is what no part holds,
``_total`` every op once.

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


COLUMNS = ("forward", "recompute", "backward", "update", "evaluate")


def measured_ms(path: str) -> tuple[dict, dict]:
    """(stage -> device self milliseconds, part -> {pass or ``evaluate`` ->
    milliseconds}) over the whole capture at ``path`` (a profile directory
    or an ``.xplane.pb``), by the benchmark's readers. Raises
    ``OSError``/``ValueError``/``RuntimeError`` on a capture that is
    missing, torn or not an xplane."""
    from benchmarks import trace_reduce
    from benchmarks.harness.spec import load_module

    stage = load_module("layer_metrics", "stage_common")
    passes = load_module("layer_metrics", "pass_common")
    xplane = (path if path.endswith(".xplane.pb")
              else trace_reduce.find_xplane(path))
    trace, tf_ops = trace_reduce.load(xplane), stage.read_tf_ops(xplane)
    by_stage = {k: v * 1e3 for k, v in stage.by_stage(trace, tf_ops).items()}
    by_part = {part: {col: s * 1e3 for col, s in cols.items()}
               for part, cols in passes.by_layer_and_pass(trace,
                                                          tf_ops).items()}
    return by_stage, by_part


def part_rows(by_part: dict) -> list[list[str]]:
    """The parts by their time, most first; ``_unscoped`` and ``_total``
    last."""
    def order(part):
        return (part == "_total", part.startswith("_"),
                -sum(by_part[part].values()), part)

    return [[part, *(f"{by_part[part][c]:.3f}" if c in by_part[part] else "-"
                     for c in COLUMNS)]
            for part in sorted(by_part, key=order)]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", metavar="PATH",
                    help="profile directory or .xplane.pb of a TPU capture")
    ap.add_argument("--json", action="store_true",
                    help="emit {measured_ms: {stage: ms}, layer_pass_ms: "
                         "{part: {pass: ms}}} as JSON instead of tables")
    args = ap.parse_args(argv)
    try:
        measured, by_part = measured_ms(args.trace)
    except (OSError, ValueError, RuntimeError) as e:
        print(f"roofline_report: cannot read trace {args.trace}: {e}",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({"measured_ms": measured,
                          "layer_pass_ms": by_part}, indent=2))
        return 0
    total = sum(measured.values())
    rows = [
        [stage, f"{ms:.3f}", f"{ms / total:.1%}" if total else "-"]
        for stage, ms in sorted(measured.items(), key=lambda kv: -kv[1])
    ]
    print(perf_report._render_generic_table(
        ("stage", "device_ms", "share"), rows))
    if by_part:
        print("\nlocal_train by part and pass, and evaluate by part "
              "(device_ms)")
        print(perf_report._render_generic_table(
            ("part", *COLUMNS), part_rows(by_part)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
