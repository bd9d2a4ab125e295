"""Shared by the six span metrics: the program's own spans in the profiler's
trace.

Every span of the program's tracer (``fl4health_tpu/observability/spans.py``)
is also a ``TraceAnnotation`` named ``fl::<span>``, so it lies on its thread's
line of ``/host:CPU`` on the clock of the device planes. ``Trace.host_lines``
keeps each event's name, start and end per thread; that is all these readers
use (the ``round`` an annotation carries is for the timeline's reader). Only
spans inside the window (the harness's ``bench_fit_call`` spans) count. A
trace of a program without these annotations has none, and every reader
returns None.
"""

from benchmarks import trace_reduce

PREFIX = "fl::"


def spans(trace, name: str) -> dict:
    """thread (key of ``host_lines``) -> [(start, end)] in start order, of
    the ``fl::<name>`` spans that lie inside the window."""
    lo, hi = trace.window
    out = {}
    for thread, events in trace.host_lines.items():
        found = sorted((e.start, e.end) for e in events
                       if e.name == PREFIX + name
                       and e.start >= lo and e.end <= hi)
        if found:
            out[thread] = found
    return out


def total_ms(trace, name: str):
    """Summed milliseconds of a span over all threads, or None without one."""
    found = spans(trace, name)
    if not found:
        return None
    return sum(e - s for evs in found.values() for s, e in evs) / 1e6


def ms_per_round(ctx, name: str):
    ms = total_ms(ctx["trace"], name)
    return ms / ctx["rounds"] if ms is not None and ctx["rounds"] else None


def mean_ms(trace, name: str, inside: str | None = None):
    """Mean milliseconds of ``name`` per occurrence, or, with ``inside``,
    of the ``name`` spans that lie within an ``inside`` span of the same
    thread, per ``inside`` span."""
    found = spans(trace, name)
    if inside is None:
        counted = [e - s for evs in found.values() for s, e in evs]
        return sum(counted) / 1e6 / len(counted) if counted else None
    outer = spans(trace, inside)
    n = sum(len(v) for v in outer.values())
    if not n:
        return None
    ns = sum(e - s for thread, evs in outer.items()
             for s, e in found.get(thread, ())
             if any(lo <= s and e <= hi for lo, hi in evs))
    return ns / 1e6 / n


def less_ms(trace, name: str, less: str):
    """Summed milliseconds of ``name`` spans minus the part of them that
    ``less`` spans of the same thread cover, or None without a ``name``."""
    found = spans(trace, name)
    if not found:
        return None
    covered = spans(trace, less)
    ns = 0.0
    for thread, evs in found.items():
        own = trace_reduce.merge(evs)
        ns += trace_reduce.total(trace_reduce.subtract(
            own, trace_reduce.merge(covered.get(thread, ()))))
    return ns / 1e6
