"""`sd_thumbnail_device_bytes_total{dir=h2d|d2h}` per thumbnail: the
`nbytes` of the canvases the thumbnailer puts on the device and of the
output canvases it fetches back, padding included. None on a program
without the counter."""


def read(ctx):
    counters = ctx["counters"]
    moved = (counters.get("sd_thumbnail_device_bytes_total{dir=h2d}", 0.0)
             + counters.get("sd_thumbnail_device_bytes_total{dir=d2h}", 0.0))
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not moved or not images:
        return None
    return moved / images
