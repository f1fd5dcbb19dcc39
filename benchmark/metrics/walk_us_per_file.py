"""Indexer job seconds (its own report) per file of the location."""


def read(ctx):
    secs = [p["summary"]["job_seconds"].get("indexer") for p in ctx["passes"]]
    files = sum(p["files"] for p in ctx["passes"])
    if None in secs or not files:
        return None
    return 1e6 * sum(secs) / files
