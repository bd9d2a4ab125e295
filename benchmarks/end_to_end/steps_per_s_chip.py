"""Client local steps completed in the window (rounds x clients x local
steps) over the window's whole wall (first call's start to the last call's
return, results on the host) and the chips the cell uses."""


def read(ctx):
    return ctx["steps"] / ctx["wall_s"] / ctx["chips"]
