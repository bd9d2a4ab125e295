"""Host pipeline: device idle time per round inside the traced window, after
each call's prologue (``fit_prologue_ms`` has that part); the breakdown's
``idle_gaps`` says which host frame each gap fell under."""


def read(ctx):
    trace = ctx["trace"]
    if not ctx["rounds"] or not trace.devices:
        return None
    lo, hi = trace.window
    idle_ns = (hi - lo) - trace.busy_s() * 1e9
    for start, first in trace.prologues():
        chip = sorted(trace.devices)[0]
        busy = sum(e - s for s, e in trace.busy(chip)
                   if s >= start and e <= first)
        idle_ns -= (first - start) - busy
    return max(idle_ns, 0.0) / 1e6 / ctx["rounds"]
