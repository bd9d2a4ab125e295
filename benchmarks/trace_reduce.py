"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy union, idle gaps attributed to the host frame
running, program launches, per-op self time, collective and kernel sums.

Read with ``jax.profiler.ProfileData`` alone. What the trace looks like on a
TPU (checked against ``artifacts/tpu_trace_20260731_034629`` and the small
trace of today's code under ``benchmarks/fixtures``): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
program launch), ``XLA Ops`` (one event per executed HLO op, named by the
op's whole HLO text; a ``while`` or ``call`` encloses its body's ops; no
``fl_stage::`` scope or ``op_name`` reaches these names) and ``Async XLA
Ops``; one plane
``/host:CPU`` with a line per thread holding the Python tracer's frames
(``$file.py:123 func``) and ``TraceAnnotation`` spans. All events are on one
clock, in nanoseconds from the start of the session.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)
WINDOW_ANNOTATION = "bench_fit_call"


@dataclasses.dataclass(slots=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns


def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """Idle intervals of [lo, hi] given the merged busy intervals."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


def short_name(name: str) -> str:
    """``%convolution_add_fusion.12 = ...`` -> ``convolution_add_fusion``."""
    head = name.split(" ", 1)[0].split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or name[:40]


def self_times(events):
    """Per-event self time: duration less what enclosed events cover.
    Returns a list of (event, self_ns) in start order."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out, stack = [], []  # stack of [event, child_ns]
    for e in evs:
        while stack and stack[-1][0].end <= e.start:
            done, child = stack.pop()
            out.append((done, max(done.end - done.start - child, 0.0)))
        if stack:
            stack[-1][1] += min(e.end, stack[-1][0].end) - e.start
        stack.append([e, 0.0])
    while stack:
        done, child = stack.pop()
        out.append((done, max(done.end - done.start - child, 0.0)))
    return out


@dataclasses.dataclass
class DeviceLane:
    ops: list  # Event, the XLA Ops line
    async_ops: list  # Event, the Async XLA Ops line
    launches: list  # Event, the XLA Modules line


class Trace:
    """The parsed trace. ``window`` is the span of the harness's own
    ``bench_fit_call`` annotations when there are any, else all events."""

    def __init__(self, devices: dict, host_lines: dict):
        self.devices = devices  # chip index -> DeviceLane
        self.host_lines = host_lines  # thread name -> [Event]
        self.calls = sorted(
            (e for evs in host_lines.values() for e in evs
             if e.name == WINDOW_ANNOTATION), key=lambda e: e.start)
        if self.calls:
            self.window = (self.calls[0].start, self.calls[-1].end)
        else:
            every = [e for d in devices.values() for e in d.ops + d.launches]
            self.window = ((min(e.start for e in every),
                            max(e.end for e in every)) if every else (0.0, 0.0))

    # -- device ----------------------------------------------------------
    def busy(self, chip: int):
        lane = self.devices[chip]
        return clip(merge((e.start, e.end) for e in lane.ops), *self.window)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips in the trace."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(c)) for c in self.devices) / (
            1e9 * len(self.devices))

    def launches(self) -> int:
        """Program launches inside the window on the busiest chip."""
        lo, hi = self.window
        return max((sum(1 for e in d.launches if lo <= e.start < hi)
                    for d in self.devices.values()), default=0)

    def main_module(self) -> str | None:
        """Name of the program with the most device time inside the window
        (first chip): the round program, or the chunked scan."""
        if not self.devices:
            return None
        lo, hi = self.window
        acc = defaultdict(float)
        for e in self.devices[sorted(self.devices)[0]].launches:
            if e.end > lo and e.start < hi:
                acc[re.sub(r"\(\d+\)$", "", e.name)] += e.end - e.start
        return max(acc, key=acc.get) if acc else None

    def prologues(self):
        """Per harness call: (call start, start of the call's first launch of
        the main module) in ns, for the calls that have one."""
        name = self.main_module()
        if name is None:
            return []
        starts = sorted(e.start
                        for e in self.devices[sorted(self.devices)[0]].launches
                        if re.sub(r"\(\d+\)$", "", e.name) == name)
        out = []
        for call in self.calls:
            first = next((t for t in starts if call.start <= t < call.end), None)
            if first is not None:
                out.append((call.start, first))
        return out

    def op_self_seconds(self) -> dict:
        """short op name -> summed self seconds, averaged over chips."""
        lo, hi = self.window
        acc = defaultdict(float)
        for lane in self.devices.values():
            inside = [e for e in lane.ops if e.end > lo and e.start < hi]
            for e, ns in self_times(inside):
                acc[short_name(e.name)] += ns
        n = max(len(self.devices), 1)
        return {k: v / 1e9 / n for k, v in acc.items()}

    def collectives(self):
        """(total_s, exposed_s) of collective ops per chip, averaged:
        exposed is the part during which no other op ran on that chip."""
        lo, hi = self.window
        tot = exp = 0.0
        for lane in self.devices.values():
            coll, rest = [], []
            for e in lane.ops:
                (coll if COLLECTIVE.search(e.name) else rest).append(
                    (e.start, e.end))
            coll += [(e.start, e.end) for e in lane.async_ops
                     if COLLECTIVE.search(e.name)]
            # enclosing ops (while/call/conditional) are not compute beside it
            leaves = [(e.start, e.end) for e, ns in self_times(
                [Event("", s, t) for s, t in rest]) if ns >= (e.end - e.start) * 0.999]
            cm = clip(merge(coll), lo, hi)
            tot += total(cm)
            exp += total(subtract(cm, clip(merge(leaves), lo, hi)))
        n = max(len(self.devices), 1)
        return tot / 1e9 / n, exp / 1e9 / n

    # -- host ------------------------------------------------------------
    def idle_by_frame(self, program_files=frozenset(), min_gap_ns=20_000):
        """name -> idle seconds. Each idle gap of the first chip inside the
        window goes to the innermost host frame running at the gap's middle
        whose file is one of ``program_files`` (base names), else to the
        innermost frame of any file, else to ``no_host_event``. The thread
        that carries the harness's own call spans is asked first."""
        if not self.devices:
            return {}
        chip = sorted(self.devices)[0]
        idle = [(s, e) for s, e in gaps(self.busy(chip), *self.window)
                if e - s >= min_gap_ns]
        mids = [(s + e) / 2 for s, e in idle]
        lines = sorted(
            self.host_lines.values(),
            key=lambda evs: (not any(e.name == WINDOW_ANNOTATION for e in evs),
                             -len(evs)))
        names = [None] * len(idle)
        fallback = [None] * len(idle)
        for evs in lines:
            stacks = _stacks_at(
                [e for e in evs if e.name != WINDOW_ANNOTATION], mids)
            for i, stack in enumerate(stacks):
                if names[i] is not None or not stack:
                    continue
                prog = next((e for e in reversed(stack)
                             if _file_of(e.name) in program_files), None)
                if prog is not None:
                    names[i] = clean_frame(prog.name)
                elif fallback[i] is None:
                    fallback[i] = clean_frame(stack[-1].name)
        out = defaultdict(float)
        for (s, e), name, alt in zip(idle, names, fallback):
            out[name or alt or "no_host_event"] += (e - s) / 1e9
        return dict(out)


def _stacks_at(events, times):
    """For each time of the ascending ``times``: the frames open at it,
    outermost first, from one sweep over the (properly nested) events."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(evs) and evs[i].start <= t:
            while stack and stack[-1].end <= evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(list(stack))
    return out


def _file_of(name: str) -> str:
    m = re.match(r"^\$?([\w.\-]+\.py):\d+", name.strip())
    return m.group(1) if m else ""


def clean_frame(name: str) -> str:
    """``$simulation.py:2901 _run_round`` -> ``simulation.py:2901:_run_round``."""
    return re.sub(r"\s+", ":", name.strip().lstrip("$"))[:80]


def load(path: str) -> Trace:
    """Parse one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_lines = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lane = DeviceLane([], [], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        lane.ops.append(Event(ev.name, ev.start_ns,
                                              ev.start_ns + ev.duration_ns))
                elif line.name == "Async XLA Ops":
                    lane.async_ops = [
                        Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
                elif line.name == "XLA Modules":
                    lane.launches = [
                        Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
            lane.ops.sort(key=lambda e: e.start)
            devices[int(m.group(1))] = lane
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                host_lines[f"{line.name}#{i}"] = [
                    Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    return Trace(devices, host_lines)


def find_xplane(trace_dir: str) -> str:
    import glob
    import os

    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]
