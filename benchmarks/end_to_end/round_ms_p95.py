"""95th percentile over every round of the window of the time between
successive round completions, as the harness's own reporter saw them; a
call's first round counts from the call's start. The chunked scan completes
no round by itself, so a chunked cell has no reading."""

import statistics


def read(ctx):
    values = ctx["round_intervals_ms"]
    if ctx["mode"] != "pipelined_per_round" or not values:
        return None
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[-1]
