"""file_identifier job seconds (its own report) per file identified."""


def read(ctx):
    secs = [p["summary"]["job_seconds"].get("file_identifier")
            for p in ctx["passes"]]
    files = ctx["hashed"]["files"]
    if None in secs or not files:
        return None
    return 1e6 * sum(secs) / files
