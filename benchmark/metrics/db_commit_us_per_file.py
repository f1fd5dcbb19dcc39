"""`sd_db_commit_seconds` per file of the location: of
`db_txn_us_per_file`, the part inside `COMMIT` (the outermost block's own,
the span `db.txn.commit`, and those of the blocks nested in it). The rest
of a transaction is its body: the statements and the Python that builds
their rows. None on a program that does not time its commits."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_db_commit_seconds"
    files = sum(p["files"] for p in ctx["passes"])
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
