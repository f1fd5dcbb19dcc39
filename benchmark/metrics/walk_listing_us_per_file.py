"""`sd_indexer_walk_seconds{part=scan}` per file of the location: the
walker's directory loop (`scandir`, a `stat` per entry, the isolated
paths, the ancestor backfill) less the rule matching and the one
`file_path` query a directory it holds, which have readers of their own
(`walk_rules_us_per_file`, `walk_fetch_us_per_file`). With them,
`walk_journal_us_per_file` and `{part=diff}` it adds up to
`walk_scan_us_per_file`. None on a program that does not split the walk."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_indexer_walk_seconds{part=scan}"
    files = sum(p["files"] for p in ctx["passes"])
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
