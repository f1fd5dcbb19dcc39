"""The `indexer.save` spans (one per save, update or remove step of up to
1,000 rows: CRDT ops built, rows and ops written in one transaction) per
file of the location."""

from benchmark.span_reduce import counter


def read(ctx):
    secs = counter(ctx["counters"], "indexer.save")
    files = sum(p["files"] for p in ctx["passes"])
    if not secs or not files:
        return None
    return 1e6 * secs / files
