"""The `journal.record` spans (`IndexJournal.record_many` after each
window's link-commit: one msgpack payload per row with the chunk
cache's digests, one `executemany` and its commit) per file hashed.
None on a program without the span."""

from benchmark.span_reduce import counter


def read(ctx):
    secs = counter(ctx["counters"], "journal.record")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
