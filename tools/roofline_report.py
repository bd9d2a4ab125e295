#!/usr/bin/env python
"""The roofline ledger: rank spine stages by fusion headroom.

ROADMAP item 5 gates fused-kernel work on "profiles showing XLA leaving
MXU/HBM throughput on the table". This CLI is that go/no-go artifact: it
reads the per-stage attribution records (``stage`` events written by
``observability/introspect.py`` from the ``observability/hloscan.py``
walk) out of a ``metrics.jsonl`` log and prints one ledger row per
(program, stage) — attributed flops/bytes, arithmetic intensity, the
compute- vs HBM-bound classification against the chip's roofline, and the
fusion headroom a hand-fused kernel could at most recover — ranked most
headroom first.

Analytic numbers work on any box (the attribution is a build-time property
of the compiled program — no device run needed). When a real profiler
capture exists, ``--trace`` adds measured per-stage device time. On a TPU
the ``fl_stage::`` scope is not in an op event's name (that is its HLO
text) but in the event metadata's ``tf_op`` stat of the raw ``.xplane.pb``;
the benchmark's reader (``benchmarks/layer_metrics/stage_common.py`` over
``benchmarks/xplane_meta.py``) sums each op's self time under the innermost
scope of its name stack, so ``--trace`` takes the profile directory
(``jax.profiler.start_trace``'s) or the ``.xplane.pb`` itself.

Honesty rules (the repo-wide None-never-0.0 discipline):

- the ``bound`` classification needs the chip's peak flops + HBM bandwidth
  (observability/device_specs.py); unknown chips print '-' — a fabricated
  MFU or ridge point is worse than none;
- a stage containing custom calls (Pallas) has cost-model-invisible flops;
  the ledger shows the ``custom_calls`` count so the blind spot is on the
  page.

    python tools/roofline_report.py artifacts/obs/metrics.jsonl
    python tools/roofline_report.py metrics.jsonl --trace artifacts/obs/xprof
    python tools/roofline_report.py metrics.jsonl --json

Exit codes: 0 ok, 1 no stage events in the log (attribution off or
pre-attribution log), 2 unreadable log/trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import perf_report  # noqa: E402  (the shared table machinery)


def rank_stages(stages: list[dict]) -> list[dict]:
    """Most fusion headroom first — the order kernel work should be
    considered in. ``_unattributed`` sinks to the bottom: it is not a
    fusable stage, only the conservation remainder."""
    def key(rec: dict):
        tail = rec.get("stage") == "_unattributed"
        return (tail, -float(rec.get("fusion_headroom_bytes") or 0.0),
                -float(rec.get("flops") or 0.0))

    return sorted(stages, key=key)


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


def attach_measured(stages: list[dict],
                    measured: dict[str, float]) -> list[dict]:
    """Fold measured per-stage device time (ms) into the ledger rows.
    Stages absent from the capture keep no ``measured_ms`` field — '-' in
    the table, absent in ``--json`` (never a fake zero)."""
    out = []
    for rec in stages:
        if rec.get("stage") in measured:
            rec = {**rec, "measured_ms": measured[rec["stage"]]}
        out.append(rec)
    return out


def render_ledger(stages: list[dict], measured: bool) -> str:
    def fmt(rec: dict, field: str, spec: str = "{:.4g}") -> str:
        v = rec.get(field)
        if v is None or (isinstance(v, float) and v != v):
            return "-"
        if isinstance(v, str):
            return v
        return spec.format(float(v))

    headers = ["rank", "program", "stage", "flops", "bytes", "intensity",
               "ridge", "bound", "headroom", "headroom%", "custom_calls"]
    if measured:
        headers.append("measured_ms")
    rows = []
    for n, rec in enumerate(stages, 1):
        row = [
            str(n),
            str(rec.get("program", "-")),
            str(rec.get("stage", "-")),
            fmt(rec, "flops"),
            fmt(rec, "bytes_accessed"),
            fmt(rec, "intensity_flops_per_byte", "{:.3g}"),
            fmt(rec, "ridge_flops_per_byte", "{:.3g}"),
            fmt(rec, "bound"),
            fmt(rec, "fusion_headroom_bytes"),
            fmt(rec, "fusion_headroom_frac", "{:.1%}"),
            fmt(rec, "custom_calls", "{:.0f}"),
        ]
        if measured:
            row.append(fmt(rec, "measured_ms", "{:.2f}"))
        rows.append(row)
    return perf_report._render_generic_table(tuple(headers), rows)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log", help="path to metrics.jsonl (or a bundle's "
                                "events.tail.jsonl)")
    ap.add_argument("--trace", metavar="PATH",
                    help="profile directory or .xplane.pb of a TPU capture "
                         "to fold measured per-stage device time into the "
                         "ledger")
    ap.add_argument("--json", action="store_true",
                    help="emit the ranked ledger as JSON instead of a table")
    args = ap.parse_args(argv)
    try:
        stages = perf_report.load_stage_events(args.log)
    except OSError as e:
        print(f"roofline_report: cannot read {args.log}: {e}",
              file=sys.stderr)
        return 2
    if not stages:
        print(
            f"no 'stage' events in {args.log} (stage attribution off — "
            "FL4HEALTH_STAGE_ATTRIBUTION=0 — or a pre-attribution log)",
            file=sys.stderr,
        )
        return 1
    measured = False
    if args.trace:
        try:
            stages = attach_measured(stages, measured_stage_ms(args.trace))
        except (OSError, ValueError, RuntimeError) as e:
            print(f"roofline_report: cannot read trace {args.trace}: {e}",
                  file=sys.stderr)
            return 2
        measured = any("measured_ms" in rec for rec in stages)
    ranked = rank_stages(stages)
    if args.json:
        print(json.dumps({"ledger": ranked}, indent=2))
        return 0
    print(render_ledger(ranked, measured))
    known = [r for r in ranked if r.get("bound")]
    if not known:
        print()
        print("bound classification unavailable: unknown device kind "
              "(no roofline in observability/device_specs.py) — "
              "intensities are real, ridge comparisons are not fabricated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
