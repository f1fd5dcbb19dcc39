"""`sd_db_txn_seconds` (every committed write of the library database:
`sync.write_ops`, the journal's `record_many`, job reports, vouches) per
file of the location."""


def read(ctx):
    secs = ctx["counters"].get("sd_db_txn_seconds.sum")
    files = sum(p["files"] for p in ctx["passes"])
    if not secs or not files:
        return None
    return 1e6 * secs / files
