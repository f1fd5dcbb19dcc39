"""`sd_db_changes_total` per file of the location: rows inserted, updated
or deleted by the committed writes (SQLite's `total_changes` over each
outermost transaction): `file_path`, `object`, `index_journal` and
`crdt_operation` rows, job reports. None on a program without the
counter."""


def read(ctx):
    changes = ctx["counters"].get("sd_db_changes_total")
    files = sum(p["files"] for p in ctx["passes"])
    if not changes or not files:
        return None
    return changes / files
