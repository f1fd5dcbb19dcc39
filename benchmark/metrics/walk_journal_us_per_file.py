"""`sd_indexer_walk_seconds{part=journal}` per file of the location: the
walker's journal consult, one `index_journal` query a walked file
(`journal.lookup`, and on a hit the stored entry parsed and its bytes
counted as saved). The span `walk.journal` holds the same seconds. None
on a program that does not split the walk."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_indexer_walk_seconds{part=journal}"
    files = sum(p["files"] for p in ctx["passes"])
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
