"""`sd_indexer_walk_seconds{part=rules}` per file of the location: the
seconds inside `IndexerRule.apply_all`, one clock pair a directory entry,
summed over the walk call and observed once at its end. None on a program
that does not split the walk."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_indexer_walk_seconds{part=rules}"
    files = sum(p["files"] for p in ctx["passes"])
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
