"""Program build: seconds in backend compiles (persistent-cache loads
included) during set-up, from jax.monitoring."""


def read(ctx):
    return ctx["compile_s"]
