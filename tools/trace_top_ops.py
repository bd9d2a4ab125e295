"""Summarize a captured jax.profiler Chrome trace: where does device time go?

tools/tpu_trace.py writes artifacts/tpu_trace_<ts>/.../vm.trace.json.gz
(standard Chrome tracing JSON). This reads one and prints, per device
thread lane, total duration and the top-N ops by aggregate self time —
the poor man's TensorBoard-profile "TensorFlow ops" view, runnable on a
box where the tensorboard profile plugin can't be installed.

    python tools/trace_top_ops.py [trace.json.gz] [--top 15]

Per-stage device time is not here: a TPU trace names its ops by HLO text,
and the ``fl_stage::`` scope sits in the raw ``.xplane.pb``'s event metadata
— ``tools/roofline_report.py`` reads it from there.

Exit codes follow the bundle-CLI convention: 0 ok, 1 no trace found,
2 unreadable/corrupt/torn trace (with a diagnostic, never a traceback).

No reference counterpart (SURVEY §5: the reference has no profiling);
companion to tools/tpu_trace.py.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TraceError(Exception):
    """Trace file missing structure / undecodable — CLI exit 2."""


def find_latest_trace() -> str | None:
    hits = sorted(glob.glob(os.path.join(
        REPO, "artifacts", "tpu_trace_*", "plugins", "profile", "*",
        "*.trace.json.gz")))
    return hits[-1] if hits else None


def load(path: str) -> dict:
    """Read a Chrome-trace JSON (optionally gzipped). Raises
    :class:`TraceError` with a diagnostic on gzip corruption, torn/invalid
    JSON, or a JSON document that is not a trace object."""
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt") as f:
            trace = json.load(f)
    except (OSError, EOFError, UnicodeDecodeError) as e:
        raise TraceError(f"cannot read trace {path}: {e}") from e
    except json.JSONDecodeError as e:
        raise TraceError(
            f"corrupt/torn trace {path}: invalid JSON at char {e.pos} "
            f"({e.msg})"
        ) from e
    if not isinstance(trace, dict):
        raise TraceError(
            f"corrupt trace {path}: top level is "
            f"{type(trace).__name__}, expected a Chrome-trace object"
        )
    return trace


def summarize(trace: dict, top: int = 15) -> list[str]:
    events = trace.get("traceEvents", [])
    # metadata: pid -> process name, (pid, tid) -> thread name
    pname: dict = {}
    tname: dict = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            tname[(e["pid"], e.get("tid"))] = e["args"]["name"]

    # complete events: aggregate duration by (lane, op name)
    lanes: dict = defaultdict(lambda: defaultdict(float))
    lane_total: dict = defaultdict(float)
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        lane = (pname.get(e["pid"], str(e["pid"])),
                tname.get((e["pid"], e.get("tid")), str(e.get("tid"))))
        lanes[lane][e.get("name", "?")] += e["dur"]
        lane_total[lane] += e["dur"]

    out = []
    for lane in sorted(lane_total, key=lane_total.get, reverse=True):
        total_ms = lane_total[lane] / 1e3
        out.append(f"== {lane[0]} / {lane[1]}: {total_ms:.2f} ms busy ==")
        ops = lanes[lane]
        for name, dur in sorted(ops.items(), key=lambda kv: -kv[1])[:top]:
            out.append(
                f"  {dur / 1e3:9.2f} ms  {100 * dur / lane_total[lane]:5.1f}%"
                f"  {name[:90]}"
            )
    return out


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    top = 15
    if "--top" in sys.argv:
        top = int(sys.argv[sys.argv.index("--top") + 1])
    path = args[0] if args else find_latest_trace()
    if not path:
        print("no trace found (run tools/tpu_trace.py first)", file=sys.stderr)
        return 1
    if not os.path.exists(path):
        print(f"trace not found: {path}", file=sys.stderr)
        return 2
    try:
        trace = load(path)
    except TraceError as e:
        print(str(e), file=sys.stderr)
        return 2
    print(f"# {path}")
    for line in summarize(trace, top):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
