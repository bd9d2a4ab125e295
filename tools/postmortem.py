#!/usr/bin/env python
"""Render a postmortem bundle into an incident report.

The flight recorder (``fl4health_tpu/observability/flightrec.py``) publishes
a ``postmortem_<ts>/`` directory on every abnormal ``fit()`` end
(``observability/bundle.py``). This tool turns one into the report an
incident review starts from — with NO access to the process that died:

    python tools/postmortem.py artifacts/obs/postmortem_20260804_120000
    python tools/postmortem.py <bundle_dir> --json

Sections: the verdict (what killed the run, which round, which clients —
REGISTRY ids under cohort-slot execution), the run facts, the recorded
round timeline (rendered with ``tools/perf_report.py``'s table machinery),
divergence-onset detection over the ring's loss trajectory, a
suspect-client ranking (grad/update-norm outliers, non-finite counts,
quarantine strikes — scored across the ring's telemetry), wire/compression
byte totals, and what to resume from (the newest durable checkpoint
generation the dead run published).

No third-party deps (zero-egress box): stdlib + numpy + the package's own
readers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import perf_report  # noqa: E402  (the shared table machinery)

# THE scoring (resilience/suspects.py) — shared with the in-process
# RecoverySupervisor so the machine quarantines exactly the clients this
# report would have named
from fl4health_tpu.resilience.suspects import (  # noqa: E402
    DIVERGENCE_FACTOR,
    client_ids_for_entry as _client_ids,
    detect_divergence_onset,
    rank_suspects,
)


def ring_round_rows(ring: list[dict]) -> list[dict]:
    """The ring entries' scalar summaries, augmented with the recorded
    losses — the rows ``perf_report.render_table`` renders."""
    rows = []
    for entry in ring:
        row = dict(entry.get("summary") or {})
        row.setdefault("round", entry.get("round"))
        if entry.get("fit_loss") is not None:
            row["fit_loss"] = entry["fit_loss"]
        if entry.get("eval_loss") is not None:
            row["eval_loss"] = entry["eval_loss"]
        rows.append(row)
    return sorted(rows, key=lambda r: r.get("round", 0))


def wire_stats(ring: list[dict]) -> dict:
    rows = ring_round_rows(ring)
    out: dict[str, Any] = {
        "broadcast_bytes": int(sum(r.get("broadcast_bytes", 0)
                                   for r in rows)),
        "gather_bytes": int(sum(r.get("gather_bytes", 0) for r in rows)),
    }
    wired = [r for r in rows if r.get("gather_bytes_wire") is not None]
    if wired:
        out["gather_bytes_wire"] = int(sum(r["gather_bytes_wire"]
                                           for r in wired))
        logical = sum(r.get("gather_bytes", 0) for r in wired)
        if out["gather_bytes_wire"] > 0:
            out["wire_compression_ratio"] = round(
                logical / out["gather_bytes_wire"], 2
            )
    return out


def build_report(bundle: dict) -> dict:
    """The machine-readable incident report (``--json`` emits exactly
    this; the text renderer walks it)."""
    ring = bundle.get("ring") or []
    verdict = bundle.get("verdict") or {}
    header = bundle.get("ring_header") or {}
    fleet = bundle.get("fleet")
    report: dict[str, Any] = {
        "bundle": bundle.get("path"),
        "verdict": verdict,
        "run": header.get("run") or {},
        "window": header.get("window"),
        "rounds_recorded": [int(e.get("round", 0)) for e in ring],
        "timeline": ring_round_rows(ring),
        "divergence_onset": detect_divergence_onset(ring),
        # fleet.json priors make repeat offenders outrank first-timers
        # with equal window evidence (absent on pre-ledger bundles)
        "suspects": rank_suspects(ring, ledger=fleet),
        "wire": wire_stats(ring),
    }
    if fleet:
        clients = fleet.get("clients") or []
        part = [int(c.get("rounds_participated") or 0) for c in clients]
        report["fleet"] = {
            "rounds_absorbed": fleet.get("rounds_absorbed"),
            "clients_seen": len(clients),
            "registry_size": fleet.get("registry_size"),
            "quarantined_now": sum(
                1 for c in clients if c.get("quarantined")),
            "max_rounds_participated": max(part) if part else 0,
        }
    ck = header.get("checkpoint") or verdict.get("resume") or {}
    if ck:
        report["resume_from"] = {
            k: ck.get(k)
            for k in ("path", "generation", "round", "kind", "bytes")
            if ck.get(k) is not None
        }
    if bundle.get("manifest"):
        mani = bundle["manifest"]
        report["manifest"] = {
            k: mani.get(k)
            for k in ("execution_mode", "backend", "device_kind",
                      "config_hash", "jax_version")
            if k in mani
        }
    return report


def render_text(report: dict) -> str:
    lines: list[str] = []
    v = report["verdict"]
    lines.append("POSTMORTEM  " + str(report.get("bundle", "")))
    lines.append("=" * max(len(lines[0]), 10))
    kind = v.get("kind", "exception")
    head = f"verdict: {kind}"
    if v.get("round") is not None:
        head += f" at round {v['round']}"
    if v.get("check"):
        head += f" (check: {v['check']})"
    if v.get("signal"):
        head += f" (signal: {v['signal']})"
    lines.append(head)
    if v.get("clients"):
        ids = ", ".join(str(c) for c in v["clients"])
        space = ("registry ids" if "slot_clients" in v else "client ids")
        lines.append(f"implicated clients ({space}): {ids}")
    if v.get("silos"):
        lines.append("silo outcomes:")
        for s in v["silos"]:
            state = "ok" if s.get("ok") else f"FAILED ({s.get('reason')})"
            lines.append(
                f"  {s['silo']}: {state} after {s.get('attempts')} "
                f"attempt(s), {s.get('elapsed_s')}s"
            )
    if v.get("message"):
        lines.append(f"message: {v['message']}")
    if v.get("epilogues_through_round") is not None:
        lines.append("epilogues completed through round "
                     f"{v['epilogues_through_round']}")
    run = report.get("run") or {}
    if run:
        facts = ", ".join(f"{k}={run[k]}" for k in sorted(run)
                          if run[k] is not None)
        lines.append(f"run: {facts}")
    rounds = report.get("rounds_recorded") or []
    lines.append(
        f"flight ring: {len(rounds)} round(s) recorded"
        + (f" ({rounds[0]}..{rounds[-1]}, window "
           f"{report.get('window')})" if rounds else "")
    )
    lines.append("")
    if report["timeline"]:
        lines.append("round timeline (flight ring):")
        lines.append(perf_report.render_table(report["timeline"]))
        lines.append("")
    onset = report.get("divergence_onset")
    if onset:
        lines.append(
            f"divergence onset: round {onset['round']} — {onset['reason']} "
            f"(loss {onset['loss']}, prior best {onset['best']}); the ring "
            "holds only the tail — onset may predate the window"
        )
    else:
        lines.append("divergence onset: none detected in the recorded "
                     "window")
    suspects = report.get("suspects") or []
    if suspects:
        lines.append("")
        lines.append("suspect clients (most suspect first):")
        for s in suspects:
            lines.append(f"  client {s['client']}  score {s['score']}")
            for e in s["evidence"]:
                lines.append(f"    - {e}")
    fleet = report.get("fleet")
    if fleet:
        lines.append("")
        lines.append(
            "fleet ledger: "
            f"{fleet.get('clients_seen')} client(s) seen over "
            f"{fleet.get('rounds_absorbed')} round(s)"
            + (f" (registry {fleet['registry_size']})"
               if fleet.get("registry_size") else "")
            + f", {fleet.get('quarantined_now', 0)} quarantined at death"
        )
    wire = report.get("wire") or {}
    if wire.get("gather_bytes"):
        lines.append("")
        w = (f"wire: broadcast {wire['broadcast_bytes']} B, gather "
             f"{wire['gather_bytes']} B")
        if wire.get("gather_bytes_wire") is not None:
            w += (f", compressed gather {wire['gather_bytes_wire']} B "
                  f"({wire.get('wire_compression_ratio')}x)")
        lines.append(w)
    resume = report.get("resume_from")
    lines.append("")
    if resume:
        lines.append(
            "resume from: generation "
            f"{resume.get('generation')} (round {resume.get('round')}) at "
            f"{resume.get('path')}"
        )
    else:
        lines.append("resume from: no durable checkpoint recorded — this "
                     "run restarts from scratch")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("bundle", help="path to a postmortem_<ts>/ directory")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report as JSON")
    args = ap.parse_args(argv)
    from fl4health_tpu.observability.bundle import load_bundle

    try:
        bundle = load_bundle(args.bundle)
    except Exception as e:  # noqa: BLE001 — operator CLI: a corrupt ring
        # frame, torn verdict JSON or missing dir is a diagnostic, never a
        # traceback (bundles come off dying machines)
        print(f"postmortem: cannot read bundle {args.bundle}: {e}",
              file=sys.stderr)
        return 2
    report = build_report(bundle)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(render_text(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
