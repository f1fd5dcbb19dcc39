"""`sd_thumbnail_stage_seconds{stage=device}` per thumbnail: the host's
wall around the device resize of a chunk; beside `resize_kernel_ms` (the
device's own time per dispatch) it shows transfer and dispatch."""


def read(ctx):
    secs = ctx["counters"].get("sd_thumbnail_stage_seconds{stage=device}.sum")
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not secs or not images:
        return None
    return 1e3 * secs / images
