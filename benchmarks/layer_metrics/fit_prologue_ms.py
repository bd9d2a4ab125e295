"""Entry layer: mean time from the start of a ``fit()`` call (the harness's
own ``bench_fit_call`` span) to the call's first launch of the window's main
program (the round program, or the chunked scan): manifest, build-time
introspection, worker threads, staging."""


def read(ctx):
    waits = [(first - start) / 1e6 for start, first in ctx["trace"].prologues()]
    return sum(waits) / len(waits) if waits else None
