"""`sd_db_read_seconds_total` per file of the location: the seconds inside
`execute(...).fetch*()` of the reads `db_reads_per_file` counts, under the
connection's lock. None on a program that does not count its reads."""


def read(ctx):
    secs = ctx["counters"].get("sd_db_read_seconds_total")
    files = sum(p["files"] for p in ctx["passes"])
    if not secs or not files:
        return None
    return 1e6 * secs / files
