"""The `walk` span (directory scan and journal consult, no save) per file
of the location: the indexer job without its save transactions."""

from benchmark.span_reduce import counter


def read(ctx):
    secs = counter(ctx["counters"], "walk")
    files = sum(p["files"] for p in ctx["passes"])
    if not secs or not files:
        return None
    return 1e6 * secs / files
