"""media_processor job seconds (its own report) per image."""


def read(ctx):
    secs = [p["summary"]["job_seconds"].get("media_processor")
            for p in ctx["passes"]]
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if None in secs or not images:
        return None
    return 1e3 * sum(secs) / images
