"""Host pipeline: device program launches (events of the trace's ``XLA
Modules`` line, busiest chip) per round of the traced window."""


def read(ctx):
    n = ctx["trace"].launches()
    return n / ctx["rounds"] if n and ctx["rounds"] else None
