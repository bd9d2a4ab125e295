"""Device: share of the traced window in which no op ran on the device
(union of op intervals, averaged over the chips used)."""


def read(ctx):
    trace = ctx["trace"]
    if trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
