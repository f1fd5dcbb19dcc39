"""`sd_thumbnail_device_seconds{part=put}` + `{part=get}` per thumbnail:
the host's wall for the canvases' way to the device, blocked to its end,
and for the output canvases' way back. None on a program without the
counter."""


def read(ctx):
    counters = ctx["counters"]
    secs = (counters.get("sd_thumbnail_device_seconds{part=put}", 0.0)
            + counters.get("sd_thumbnail_device_seconds{part=get}", 0.0))
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not secs or not images:
        return None
    return 1e3 * secs / images
