"""Program build: backend compile events inside the window (expected 0; a
run with any is not ``correct``)."""


def read(ctx):
    return ctx["compiles_in_window"]
