"""`sd_thumbnail_work_seconds{stage=encode}` per thumbnail: seconds
inside `finish()` (webp encode) on the worker threads, summed over
images."""


def read(ctx):
    secs = ctx["counters"].get("sd_thumbnail_work_seconds{stage=encode}.sum")
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not secs or not images:
        return None
    return 1e3 * secs / images
