"""Seconds per pass outside `cli.index_location`'s own clock: node start,
library creation, shutdown, the traffic's mutations (harness clock)."""


def read(ctx):
    passes = ctx["passes"]
    return sum(p["cycle_s"] - p["index_s"] for p in passes) / len(passes)
