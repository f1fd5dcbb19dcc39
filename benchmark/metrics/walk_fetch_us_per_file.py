"""The walker's `file_path` queries per file of the location:
`sd_indexer_walk_seconds{part=fetch}` (`file_paths_db_fetcher`: one
`find_one` a walked entry; the span `walk.fetch`) and `{part=remove_query}`
(`to_remove_db_fetcher`: one query a directory, a clock pair inside
`walk.scan`). None on a program that does not split the walk."""


def read(ctx):
    c = ctx["counters"]
    fetch = "sd_indexer_walk_seconds{part=fetch}"
    remove = c.get("sd_indexer_walk_seconds{part=remove_query}.sum")
    files = sum(p["files"] for p in ctx["passes"])
    if not c.get(fetch + ".count") or remove is None or not files:
        return None
    return 1e6 * (c[fetch + ".sum"] + remove) / files
