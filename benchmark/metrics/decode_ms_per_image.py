"""`sd_thumbnail_work_seconds{stage=decode}` per thumbnail: seconds
inside `decode()` on the worker threads, summed over images."""


def read(ctx):
    secs = ctx["counters"].get("sd_thumbnail_work_seconds{stage=decode}.sum")
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not secs or not images:
        return None
    return 1e3 * secs / images
