#!/usr/bin/env python
"""Render an observability JSONL metrics log into a per-round summary table.

The observability subsystem (fl4health_tpu/observability/) logs one
``round`` event per federated round into ``metrics.jsonl`` (written by
``Observability.export()``). This tool turns that log into the table a perf
investigation starts from — compile count, device/host split, wire bytes —
without opening the Perfetto trace:

    python tools/perf_report.py artifacts/obs/metrics.jsonl
    python tools/perf_report.py artifacts/obs/metrics.jsonl --json

No third-party deps (zero-egress box): plain-text alignment, stdlib only.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Iterable

COLUMNS = (
    # (header, event field, formatter)
    ("round", "round", lambda v: str(int(v))),
    ("compiles", "compiles", lambda v: str(int(v))),
    ("compile_ms", "compile_s", lambda v: f"{v * 1000:.1f}"),
    ("device_ms", "device_wait_s", lambda v: f"{v * 1000:.1f}"),
    ("host_ms", "host_s", lambda v: f"{v * 1000:.1f}"),
    ("fit_ms", "fit_s", lambda v: f"{v * 1000:.1f}"),
    ("eval_ms", "eval_s", lambda v: f"{v * 1000:.1f}"),
    ("bytes_out", "broadcast_bytes", lambda v: str(int(v))),
    ("bytes_in", "gather_bytes", lambda v: str(int(v))),
    ("clients", "participants", lambda v: str(int(v))),
    ("failures", "failures", lambda v: str(int(v))),
)

# In-graph telemetry summary fields (observability/telemetry.py). Optional:
# a column renders only when at least one round event carries the field, so
# pre-telemetry logs keep their exact old table shape.
TELEMETRY_COLUMNS = (
    ("grad_norm", "grad_norm_max", lambda v: f"{v:.3g}"),
    ("upd_norm", "update_norm_mean", lambda v: f"{v:.3g}"),
    ("clip_frac", "clip_fraction", lambda v: f"{v:.2f}"),
    ("nonfinite", "nonfinite", lambda v: str(int(v))),
    ("diverg", "divergence_max", lambda v: f"{v:.3g}"),
)

# Compressed-exchange fields (fl4health_tpu/compression/): estimated wire
# bytes of the round's gather under the active CompressionConfig and the
# logical/wire ratio. Optional like the telemetry columns — logs from
# uncompressed runs keep their exact old table shape (byte-stable, tested).
WIRE_COLUMNS = (
    ("wire_bytes", "gather_bytes_wire", lambda v: str(int(v))),
    ("wire_ratio", "wire_compression_ratio", lambda v: f"{v:.1f}x"),
)

# Mesh-run fields (parallel/program.py RoundProgramBuilder): device count,
# clients-axis width and the per-chip throughput numbers. Optional like the
# telemetry columns — single-chip logs keep their exact old table shape
# (byte-stable, tested).
MESH_COLUMNS = (
    ("chips", "mesh_devices", lambda v: str(int(v))),
    ("steps/s/chip", "steps_per_s_per_chip", lambda v: f"{v:.3g}"),
    ("tflops/chip", "tflops_per_chip", lambda v: f"{v:.3g}"),
)

# Mixed-precision fields (fl4health_tpu/precision/): the compute dtype the
# round's device time (and thus its MFU/tflops columns) is attributable to,
# and the cumulative fp16 loss-scale skipped-step count across participating
# clients. Optional like the telemetry columns — f32 logs keep their exact
# old table shape (byte-stable, tested).
PRECISION_COLUMNS = (
    ("dtype", "compute_dtype", str),
    ("ls_skips", "loss_scale_skips", lambda v: str(int(v))),
)

# Buffered-async fields (server/async_schedule.py): buffer occupancy at the
# aggregation event, consumed-update staleness and the virtual
# arrival-driven cadence. Optional like the telemetry columns — synchronous
# logs keep their exact old table shape (byte-stable, tested).
ASYNC_COLUMNS = (
    ("buffer", "async_buffer", lambda v: str(int(v))),
    ("stale_avg", "staleness_mean", lambda v: f"{v:.2f}"),
    ("stale_max", "staleness_max", lambda v: str(int(v))),
    ("cadence_vs", "async_cadence_vs", lambda v: f"{v:.3g}"),
)

# Durable-checkpoint fields (checkpointing/state.py): write wall and frame
# bytes of the round's state-checkpoint saves, folded in from `checkpoint`
# events by round. Optional like the telemetry columns — logs from runs
# without a state checkpointer keep their exact old table shape
# (byte-stable, tested).
CKPT_COLUMNS = (
    ("ckpt_ms", "ckpt_write_ms", lambda v: f"{v:.1f}"),
    ("ckpt_bytes", "ckpt_bytes", lambda v: str(int(v))),
)

# Cohort-slot fields (server/registry.py): slot occupancy, registry size
# and the host staging wall of the round's gather/scatter cycle. Optional
# like the telemetry columns — dense-path logs keep their exact old table
# shape (byte-stable, tested).
COHORT_COLUMNS = (
    ("slots", "cohort_slots", lambda v: str(int(v))),
    ("cohort", "cohort_valid", lambda v: str(int(v))),
    ("registry", "registry_size", lambda v: str(int(v))),
    ("stage_ms", "stage_ms", lambda v: f"{v:.1f}"),
    ("scatter_ms", "scatter_ms", lambda v: f"{v:.1f}"),
    # chunked-cohort execution facts (PR 17): how many rounds each device
    # dispatch covered and where the round's cohort ids were drawn ("host"
    # for the pipelined mirror, "in_graph" for the chunked scan,
    # "event_plan" for async-over-registry). Absent from pre-chunk logs,
    # so those tables stay byte-stable.
    ("rpd", "rounds_per_dispatch", lambda v: str(int(v))),
    ("draw", "cohort_draw", str),
)

# Fleet-ledger fields (observability/fleet.py): first-time participants,
# lifetime participation skew (gini over the ledger's per-client counts)
# and the p99 straggler score of the round. Optional like the telemetry
# columns — ledger-off logs keep their exact old table shape (byte-stable,
# tested).
FLEET_COLUMNS = (
    ("new_clients", "participants_new", lambda v: str(int(v))),
    ("gini", "participation_gini", lambda v: f"{v:.3f}"),
    ("strag_p99", "straggler_p99", lambda v: f"{v:.1f}"),
)

# Flight-recorder fields (observability/flightrec.py): the recorded
# aggregate losses a postmortem ring carries per round. Round events in
# normal JSONL logs never contain them, so legacy tables stay byte-stable;
# `--bundle` timelines (and only they) light these columns up.
FLIGHT_COLUMNS = (
    ("fit_loss", "fit_loss", lambda v: f"{v:.4g}"),
    ("eval_loss", "eval_loss", lambda v: f"{v:.4g}"),
)

# Operations-plane fields (observability/slo.py + adminplane.py): the SLO
# standing forward-filled from `slo` transition events, the worst
# short-window burn rate at each transition, and admin retune markers
# folded in from `admin` events by round. Optional like the telemetry
# columns — logs without an armed ops plane keep their exact old table
# shape (byte-stable, tested).
SLO_COLUMNS = (
    ("slo", "slo_state", str),
    ("burn", "slo_burn", lambda v: f"{v:.2f}"),
)
ADMIN_COLUMNS = (
    ("retune", "admin_retune", str),
)


def merge_slo_fields(rounds: list[dict],
                     slo_events: list[dict]) -> list[dict]:
    """Fold ``slo`` transition events into the round rows: the overall
    state forward-fills from each transition (the standing HOLDS between
    transitions), the burn column shows the worst short-window burn at the
    transition round itself. Rounds before the first transition stay
    untouched, and logs without ``slo`` events are returned as-is."""
    if not slo_events:
        return rounds
    by_round: dict[int, dict] = {}
    for rec in slo_events:
        r = rec.get("round")
        if r is None:
            continue
        slot = by_round.setdefault(int(r), {})
        if rec.get("state") is not None:
            slot["slo_state"] = str(rec["state"])
        if rec.get("burn_short") is not None:
            slot["slo_burn"] = max(float(slot.get("slo_burn", 0.0)),
                                   float(rec["burn_short"]))
    out = []
    state = None
    for rec in sorted(rounds, key=lambda r: r.get("round", 0)):
        rnd = int(rec.get("round", 0))
        slot = by_round.get(rnd)
        if slot is not None:
            state = slot.get("slo_state", state)
            rec = {**rec, **slot}
        elif state is not None:
            rec = {**rec, "slo_state": state}
        out.append(rec)
    return out


def merge_admin_fields(rounds: list[dict],
                       admin_events: list[dict]) -> list[dict]:
    """Fold ``admin`` retune events into the matching round rows as a
    compact ``name=value`` marker. Rounds without a retune keep no admin
    field and render '-'; logs without ``admin`` events are returned
    as-is."""
    if not admin_events:
        return rounds
    by_round: dict[int, list[str]] = {}
    for rec in admin_events:
        r = rec.get("round")
        if r is None:
            continue
        for name, value in sorted((rec.get("scalars") or {}).items()):
            by_round.setdefault(int(r), []).append(f"{name}={value:g}")
    return [
        {**rec, "admin_retune": ",".join(by_round[int(rec.get("round", 0))])}
        if int(rec.get("round", 0)) in by_round else rec
        for rec in rounds
    ]


def merge_checkpoint_fields(rounds: list[dict],
                            ckpt_events: list[dict]) -> list[dict]:
    """Fold ``checkpoint`` events' write-ms/bytes into the matching round
    rows (summed when a round publishes several frames). Rounds without a
    save — off-cadence rounds — keep no ckpt fields and render '-'."""
    if not ckpt_events:
        return rounds
    by_round: dict[int, dict] = {}
    for rec in ckpt_events:
        r = rec.get("round")
        if r is None:
            continue
        agg = by_round.setdefault(
            int(r), {"ckpt_write_ms": 0.0, "ckpt_bytes": 0}
        )
        agg["ckpt_write_ms"] += float(rec.get("write_ms", 0.0))
        agg["ckpt_bytes"] += int(rec.get("bytes", 0))
    return [
        {**rec, **by_round[int(rec.get("round", 0))]}
        if int(rec.get("round", 0)) in by_round else rec
        for rec in rounds
    ]


def load_events(path: str) -> dict[str, list[dict]]:
    """Parse the JSONL log into {event_kind: [records]}. Malformed lines
    are skipped with a note on stderr — a crash mid-append must not make
    the whole log unreadable."""
    events: dict[str, list[dict]] = {}
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                print(f"{path}:{lineno}: skipping malformed line",
                      file=sys.stderr)
                continue
            kind = rec.get("event")
            if kind:
                events.setdefault(kind, []).append(rec)
    return events


def _sorted_rounds(rounds: list[dict]) -> list[dict]:
    return sorted(rounds, key=lambda r: r.get("round", 0))


def _latest_programs(programs: list[dict]) -> list[dict]:
    """LAST report per program name (a log may hold several fits), sorted
    by name."""
    latest: dict[str, dict] = {}
    for rec in programs:
        if rec.get("name"):
            latest[rec["name"]] = rec
    return [latest[n] for n in sorted(latest)]


def load_round_events(path: str) -> list[dict]:
    """The ``round`` events of the log, sorted by round."""
    return _sorted_rounds(load_events(path).get("round", []))


def load_program_events(path: str) -> list[dict]:
    """The ``program`` introspection records (observability/introspect.py),
    deduped to the latest report per program."""
    return _latest_programs(load_events(path).get("program", []))


def active_columns(rounds: list[dict]) -> tuple:
    """Base columns plus any telemetry/wire column present in >=1 round
    event."""
    extra = tuple(
        col for col in (TELEMETRY_COLUMNS + WIRE_COLUMNS + MESH_COLUMNS
                        + PRECISION_COLUMNS + ASYNC_COLUMNS + CKPT_COLUMNS
                        + COHORT_COLUMNS + FLEET_COLUMNS + FLIGHT_COLUMNS
                        + SLO_COLUMNS + ADMIN_COLUMNS)
        if any(col[1] in rec for rec in rounds)
    )
    return COLUMNS + extra


def render_table(rounds: Iterable[dict]) -> str:
    """Aligned plain-text table; missing fields render as '-'; NaN
    telemetry values (e.g. clip fraction without DP) render as '-' too."""
    rounds = list(rounds)
    columns = active_columns(rounds)
    rows = [[h for h, _, _ in columns]]
    for rec in rounds:
        row = []
        for _, field, fmt in columns:
            v = rec.get(field)
            if v is None or (isinstance(v, float) and v != v):
                row.append("-")
            elif isinstance(v, str):
                # non-numeric fields (compute_dtype) skip the float coercion
                row.append(fmt(v))
            else:
                row.append(fmt(float(v)))
        rows.append(row)
    widths = [max(len(r[i]) for r in rows) for i in range(len(columns))]
    lines = []
    for n, row in enumerate(rows):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if n == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def _fmt_program_cell(field: str, rec: dict) -> str:
    v = rec.get(field)
    if v is None or (isinstance(v, float) and v != v):
        return "-"
    if field == "cache_hit":
        return "hit" if v else "miss"
    if field == "mesh":
        # mesh/sharding descriptor -> compact axis summary ("clients=8" /
        # "clients=4,model=2")
        axes = (v or {}).get("axes") or {}
        if not axes:
            return "-"
        return ",".join(f"{a}={int(n)}" for a, n in axes.items())
    if field == "name":
        return str(v)
    if field == "compile_seconds":
        return f"{float(v) * 1000:.1f}"
    if field in ("flops", "bytes_accessed"):
        return f"{float(v):.4g}"
    return str(int(v))


def load_fault_events(path: str) -> list[dict]:
    """The ``fault`` injection records (resilience/faults.py FaultPlan
    host mirror), sorted by round."""
    return _sorted_rounds(load_events(path).get("fault", []))


def load_quarantine_events(path: str) -> list[dict]:
    """The ``quarantine`` transition records (resilience subsystem),
    sorted by round."""
    return _sorted_rounds(load_events(path).get("quarantine", []))


def load_recovery_events(path: str) -> list[dict]:
    """The ``recovery`` supervisor records (resilience/supervisor.py:
    one ``engage`` per ladder attempt, ``probation_passed``/``halt``
    transitions), sorted by round."""
    return _sorted_rounds(load_events(path).get("recovery", []))


def _render_generic_table(headers, rows_of_cells) -> str:
    rows = [list(headers)] + [list(r) for r in rows_of_cells]
    widths = [max(len(r[i]) for r in rows) for i in range(len(headers))]
    lines = []
    for n, row in enumerate(rows):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if n == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def _ids(v: Any) -> str:
    if not v:
        return "-"
    return ",".join(str(int(c)) for c in v)


def render_fault_table(faults: list[dict]) -> str:
    """Per-round fault-injection table: which clients the active FaultPlan
    dropped/corrupted and with what attack kinds."""
    return _render_generic_table(
        ("round", "dropped", "corrupted", "kinds"),
        (
            [
                str(int(rec.get("round", 0))),
                _ids(rec.get("dropped")),
                _ids(rec.get("corrupted")),
                ",".join(sorted((rec.get("kinds") or {}).keys())) or "-",
            ]
            for rec in faults
        ),
    )


def render_quarantine_table(events: list[dict]) -> str:
    """Per-round quarantine transitions (source = in-graph strategy or
    watchdog mitigation): active count, entries, releases."""
    return _render_generic_table(
        ("round", "source", "active", "entered", "released"),
        (
            [
                str(int(rec.get("round", 0))),
                str(rec.get("source", "-")),
                str(len(rec.get("active") or [])),
                _ids(rec.get("entered")),
                _ids(rec.get("released")),
            ]
            for rec in events
        ),
    )


def render_recovery_table(events: list[dict]) -> str:
    """Recovery-supervisor attempt table: which rung handled which
    verdict, who was quarantined, and where the resume restarted.
    Rendered only when a log carries ``recovery`` events, so legacy logs
    keep their exact output shape."""
    def cell(rec, key):
        v = rec.get(key)
        return str(v) if v is not None else "-"

    return _render_generic_table(
        ("round", "phase", "attempt", "rung", "kind", "suspects",
         "resume"),
        (
            [
                cell(rec, "round"),
                str(rec.get("phase", "-")),
                cell(rec, "attempt"),
                str(rec.get("rung") or "-"),
                str(rec.get("kind") or rec.get("reason") or "-"),
                _ids(rec.get("suspects")),
                cell(rec, "resume_round"),
            ]
            for rec in events
        ),
    )


def _sorted_sweep_cells(cells: list[dict]) -> list[dict]:
    return sorted(cells, key=lambda r: r.get("cell", 0))


def render_sweep_leaderboard(cells: list[dict]) -> str:
    """The scenario-sweep leaderboard: one row per grid cell, best final
    eval loss first (NaN/missing losses last). Rendered only when a log
    carries ``sweep`` events, so legacy logs keep their exact output
    shape."""
    def fmt(v, spec="{:.4g}"):
        if v is None or (isinstance(v, float) and v != v):
            return "-"
        return spec.format(v)

    def rank(rec):
        # one float key: None and NaN both collapse to +inf (render '-',
        # sort last) — mixing them must not TypeError the whole report
        v = rec.get("final_eval_loss")
        if v is None or (isinstance(v, float) and v != v):
            return float("inf")
        return float(v)

    ranked = sorted(cells, key=rank)
    return _render_generic_table(
        ("cell", "config", "final_loss", "best_loss", "to_target",
         "steps/s", "compiles"),
        (
            [
                str(int(rec.get("cell", 0))),
                str(rec.get("label", "-")),
                fmt(rec.get("final_eval_loss")),
                fmt(rec.get("best_eval_loss")),
                ("-" if rec.get("rounds_to_target") is None
                 else str(int(rec["rounds_to_target"]))),
                fmt(rec.get("steps_per_s"), "{:.3g}"),
                fmt(rec.get("compiles_attributed"), "{:.2g}"),
            ]
            for rec in ranked
        ),
    )


def summarize_sweep(summary_events: list[dict]) -> dict[str, Any]:
    """The last ``sweep_summary`` event's compile-amortization facts."""
    if not summary_events:
        return {}
    rec = summary_events[-1]
    return {
        k: rec[k]
        for k in ("cells", "groups", "buckets", "programs_compiled",
                  "compile_s_total", "cells_per_compile", "wall_s")
        if k in rec
    }


def render_program_table(programs: list[dict]) -> str:
    """Per-compiled-program table from ``program`` introspection events:
    cost-model FLOPs/bytes, HBM footprint, compile wall, persistent-cache
    attribution."""
    fields = ("name", "flops", "bytes_accessed", "peak_hbm_bytes",
              "compile_seconds", "cache_hit")
    headers = ("program", "flops", "bytes", "hbm_peak", "compile_ms", "cache")
    if any(rec.get("mesh") for rec in programs):
        # mesh-built programs only (parallel/program.py descriptor) —
        # single-chip logs keep the exact legacy table shape
        fields = fields + ("mesh",)
        headers = headers + ("mesh",)
    rows = [list(headers)]
    for rec in programs:
        rows.append([_fmt_program_cell(f, rec) for f in fields])
    widths = [max(len(r[i]) for r in rows) for i in range(len(headers))]
    lines = []
    for n, row in enumerate(rows):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if n == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def summarize(rounds: list[dict]) -> dict[str, Any]:
    """Aggregate totals — the one-glance numbers a PR comment quotes."""
    if not rounds:
        return {"rounds": 0}
    tot = lambda k: sum(float(r.get(k, 0.0)) for r in rounds)  # noqa: E731
    steady = [r for r in rounds[1:]] or rounds  # round 1 pays the compiles
    summary = {
        "rounds": len(rounds),
        "total_compiles": int(tot("compiles")),
        "compile_s": round(tot("compile_s"), 4),
        "device_s": round(tot("device_wait_s"), 4),
        "host_s": round(tot("host_s"), 4),
        "broadcast_bytes": int(tot("broadcast_bytes")),
        "gather_bytes": int(tot("gather_bytes")),
        "steady_state_round_s": round(
            sum(float(r.get("fit_s", 0)) + float(r.get("eval_s", 0))
                for r in steady) / len(steady), 4,
        ),
        "steady_state_recompiles": int(
            sum(float(r.get("compiles", 0)) for r in rounds[1:])
        ),
    }
    if any("gather_bytes_wire" in r for r in rounds):
        # compressed-exchange runs only — legacy summaries stay byte-stable
        summary["gather_bytes_wire"] = int(tot("gather_bytes_wire"))
    if any("compute_dtype" in r for r in rounds):
        # precision runs only — the dtype the run's timing/MFU numbers are
        # attributable to (a list if a log mixes runs of different dtypes)
        dtypes = sorted({str(r["compute_dtype"]) for r in rounds
                         if "compute_dtype" in r})
        summary["compute_dtype"] = dtypes[0] if len(dtypes) == 1 else dtypes
    if any("loss_scale_skips" in r for r in rounds):
        # cumulative counter: the last round's value IS the run total
        summary["loss_scale_skips"] = int(max(
            float(r.get("loss_scale_skips", 0.0)) for r in rounds
        ))
    if any("async_cadence_vs" in r for r in rounds):
        # buffered-async runs only — mean arrival-driven cadence (virtual
        # seconds) and worst consumed-update staleness over the run
        cad = [float(r["async_cadence_vs"]) for r in rounds
               if "async_cadence_vs" in r]
        summary["async_cadence_vs"] = round(sum(cad) / len(cad), 4)
        summary["staleness_max"] = int(max(
            float(r.get("staleness_max", 0.0)) for r in rounds
        ))
    if any("mesh_devices" in r for r in rounds):
        # mesh runs only — device count plus the mean per-chip throughput
        # over the rounds that measured one
        summary["mesh_devices"] = int(max(
            float(r.get("mesh_devices", 0)) for r in rounds
        ))
        sps = [float(r["steps_per_s_per_chip"]) for r in rounds
               if "steps_per_s_per_chip" in r]
        if sps:
            summary["steps_per_s_per_chip"] = round(sum(sps) / len(sps), 4)
    if any("ckpt_bytes" in r for r in rounds):
        # checkpointed runs only — write count, total frame bytes and total
        # write wall (legacy summaries stay byte-stable)
        summary["ckpt_writes"] = sum(1 for r in rounds if "ckpt_bytes" in r)
        summary["ckpt_bytes"] = int(tot("ckpt_bytes"))
        summary["ckpt_write_ms"] = round(tot("ckpt_write_ms"), 3)
    if any("cohort_slots" in r for r in rounds):
        # cohort-slot runs only — slot/registry facts plus the mean host
        # staging/scatter walls (the overlap the slot path must hide)
        summary["cohort_slots"] = int(max(
            float(r.get("cohort_slots", 0)) for r in rounds
        ))
        summary["registry_size"] = int(max(
            float(r.get("registry_size", 0)) for r in rounds
        ))
        stage = [float(r["stage_ms"]) for r in rounds if "stage_ms" in r]
        if stage:
            summary["stage_ms_mean"] = round(sum(stage) / len(stage), 3)
        scat = [float(r["scatter_ms"]) for r in rounds
                if "scatter_ms" in r]
        if scat:
            summary["scatter_ms_mean"] = round(sum(scat) / len(scat), 3)
        if any("rounds_per_dispatch" in r for r in rounds):
            # chunked-cohort runs only — the chunk size R the run amortized
            # its host round-trips over, and the draw sites it mixed
            summary["rounds_per_dispatch"] = int(max(
                float(r.get("rounds_per_dispatch", 0)) for r in rounds
            ))
            draws = sorted({str(r["cohort_draw"]) for r in rounds
                            if "cohort_draw" in r})
            if draws:
                summary["cohort_draw"] = (
                    draws[0] if len(draws) == 1 else draws
                )
    fleet = fleet_summary(rounds)
    if fleet:
        # fleet-ledger runs only — legacy summaries stay byte-stable
        summary.update(fleet)
    return summary


def fleet_summary(rounds: list[dict]) -> "dict[str, Any] | None":
    """Fleet-ledger aggregates over the round events, or None when the
    log never carried a fleet field (ledger off / pre-ledger log). The
    gini and straggler numbers are LIFETIME statistics, so the last
    round's value is the run's current state (not a mean)."""
    if not any("participants_new" in r or "participation_gini" in r
               for r in rounds):
        return None
    out: dict[str, Any] = {
        "fleet_new_clients": int(sum(
            float(r.get("participants_new", 0)) for r in rounds
        )),
    }
    ginis = [float(r["participation_gini"]) for r in rounds
             if r.get("participation_gini") is not None]
    if ginis:
        out["participation_gini"] = round(ginis[-1], 4)
    strag = [float(r["straggler_p99"]) for r in rounds
             if r.get("straggler_p99") is not None]
    if strag:
        out["straggler_p99"] = round(strag[-1], 2)
    return out


def render_bundle(bundle_dir: str, as_json: bool = False) -> int:
    """``--bundle``: render a postmortem bundle's flight ring with the
    SAME per-round table machinery the JSONL log gets — the quick look
    before ``tools/postmortem.py``'s full incident report."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:  # script invocation: tools/ is sys.path[0]
        sys.path.insert(0, repo)
    from fl4health_tpu.observability.bundle import load_bundle

    try:
        bundle = load_bundle(bundle_dir)
    except Exception as e:  # noqa: BLE001 — operator CLI: a corrupt ring
        # frame (CheckpointCorruptError), torn verdict JSON or missing dir
        # is a diagnostic, never a traceback
        print(f"perf_report: cannot read bundle {bundle_dir}: {e}",
              file=sys.stderr)
        return 2
    rows = []
    for entry in sorted(bundle.get("ring") or [],
                        key=lambda e: e.get("round", 0)):
        row = dict(entry.get("summary") or {})
        row.setdefault("round", entry.get("round"))
        for k in ("fit_loss", "eval_loss"):
            if entry.get(k) is not None:
                row[k] = entry[k]
        rows.append(row)
    verdict = bundle.get("verdict") or {}
    if as_json:
        print(json.dumps({"verdict": verdict, "rounds": rows}, indent=2,
                         default=str))
        return 0
    kind = verdict.get("kind", "?")
    head = f"postmortem bundle: {bundle_dir} (verdict: {kind}"
    if verdict.get("round") is not None:
        head += f", round {verdict['round']}"
    print(head + ")")
    if not rows:
        print("flight ring is empty (the run died before any round's "
              "epilogue)", file=sys.stderr)
        return 1
    print(render_table(rows))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log", nargs="?", help="path to metrics.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    ap.add_argument("--sweep", action="store_true",
                    help="render only the scenario-sweep leaderboard "
                         "(fl4health_tpu/sweep/ 'sweep' events)")
    ap.add_argument("--bundle", metavar="DIR",
                    help="render a postmortem bundle's flight ring "
                         "(observability/bundle.py postmortem_<ts>/ dir) "
                         "instead of a JSONL log")
    args = ap.parse_args(argv)
    if args.bundle:
        return render_bundle(args.bundle, as_json=args.json)
    if not args.log:
        ap.error("a metrics.jsonl path is required (or --bundle DIR)")
    try:
        events = load_events(args.log)  # ONE parse serves every table
        rounds = _sorted_rounds(events.get("round", []))
        programs = _latest_programs(events.get("program", []))
        faults = _sorted_rounds(events.get("fault", []))
        quarantine = _sorted_rounds(events.get("quarantine", []))
        recovery = _sorted_rounds(events.get("recovery", []))
        sweep_cells = _sorted_sweep_cells(events.get("sweep", []))
        sweep_summary = summarize_sweep(events.get("sweep_summary", []))
        checkpoints = _sorted_rounds(events.get("checkpoint", []))
        slo_events = _sorted_rounds(events.get("slo", []))
        admin_events = _sorted_rounds(events.get("admin", []))
        rounds = merge_checkpoint_fields(rounds, checkpoints)
        rounds = merge_slo_fields(rounds, slo_events)
        rounds = merge_admin_fields(rounds, admin_events)
    except OSError as e:
        # a missing/unreadable log is an error exit, not a traceback
        print(f"perf_report: cannot read {args.log}: {e}", file=sys.stderr)
        return 2
    def emit_sweep_only() -> int:
        # one emission shape for both sweep-only entry paths (--sweep and
        # the no-round-events fallback)
        if args.json:
            print(json.dumps({"sweep_summary": sweep_summary,
                              "sweep": sweep_cells}, indent=2))
            return 0
        print(render_sweep_leaderboard(sweep_cells))
        if sweep_summary:
            print()
            for k, v in sweep_summary.items():
                print(f"{k}: {v}")
        return 0

    if args.sweep:
        if not sweep_cells:
            print(f"no 'sweep' events in {args.log}", file=sys.stderr)
            return 1
        return emit_sweep_only()
    if not rounds:
        # empty or fully-unparseable JSONL: loud non-zero exit, never an
        # empty table a CI grep would happily accept — unless the log is a
        # sweep-only run, whose leaderboard IS its round table
        if sweep_cells:
            return emit_sweep_only()
        print(f"no 'round' events in {args.log}", file=sys.stderr)
        return 1
    if args.json:
        doc = {"summary": summarize(rounds), "rounds": rounds}
        if programs:
            doc["programs"] = programs
        if faults:
            doc["faults"] = faults
        if quarantine:
            doc["quarantine"] = quarantine
        if recovery:
            doc["recovery"] = recovery
        if sweep_cells:
            doc["sweep"] = sweep_cells
            doc["sweep_summary"] = sweep_summary
        if checkpoints:
            doc["checkpoints"] = checkpoints
        if slo_events:
            # ops-plane runs only — legacy JSON keeps its exact shape
            doc["slo"] = slo_events
        if admin_events:
            doc["admin"] = admin_events
        fleet = fleet_summary(rounds)
        if fleet:
            # fleet-ledger runs only — legacy JSON keeps its exact shape
            doc["fleet"] = fleet
        print(json.dumps(doc, indent=2))
        return 0
    print(render_table(rounds))
    if programs:
        # ProgramReport records present (introspection was on): one row per
        # compiled program — legacy logs keep the exact old output shape
        print()
        print(render_program_table(programs))
    if faults:
        # resilience chaos layer active: disclose what was injected
        print()
        print(render_fault_table(faults))
    if quarantine:
        print()
        print(render_quarantine_table(quarantine))
    if recovery:
        # recovery-supervisor runs only: one row per ladder attempt /
        # probation transition — legacy logs keep the exact old shape
        print()
        print(render_recovery_table(recovery))
    if sweep_cells:
        # scenario-sweep runs only: the leaderboard rides along — legacy
        # logs keep the exact old output shape (byte-stable, tested)
        print()
        print(render_sweep_leaderboard(sweep_cells))
    print()
    for k, v in summarize(rounds).items():
        print(f"{k}: {v}")
    if sweep_summary:
        for k, v in sweep_summary.items():
            print(f"sweep_{k}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
