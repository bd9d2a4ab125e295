"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip the cell uses,
read after the window and before the reference runs."""


def read(ctx):
    return ctx["peak_bytes"] / 1024 ** 3
