"""`sd_db_reads_total` per file of the location: every `LibraryDb.query`
and `query_one` (so `find`, `find_one`, `count`): the walker's lookup of
each entry and its journal consult, the row loop's, the page and the link
queries. A pass closes its library inside the window, so the window holds
every read. None on a program that does not count its reads."""


def read(ctx):
    reads = ctx["counters"].get("sd_db_reads_total")
    files = sum(p["files"] for p in ctx["passes"])
    if not reads or not files:
        return None
    return reads / files
