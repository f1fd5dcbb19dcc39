"""`sd_thumbnail_device_seconds{part=pack}` per thumbnail: allocating
(or reusing) and filling the staging canvases, the first part of the
`thumbnail.device` span. None on a program without the counter."""


def read(ctx):
    secs = ctx["counters"].get("sd_thumbnail_device_seconds{part=pack}")
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not secs or not images:
        return None
    return 1e3 * secs / images
