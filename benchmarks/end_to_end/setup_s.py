"""Process start to the window's start: imports, backend, data and weights,
build, the check calls (compile or cache load) and warm-up."""


def read(ctx):
    return ctx["setup_s"]
