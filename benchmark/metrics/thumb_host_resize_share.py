"""`sd_thumbnail_host_resize_total{reason}` per thumbnail generated, in
percent: the stills PIL resized on a host thread (a target beyond the
output canvases, a frame beyond the rungs, a failed device stage) and
not the chip. 0 where the resize stayed on the chip. None on a program
without the counters of ISSUE 38 (it is told by
`sd_thumbnail_frames_total`, which every decoded frame ticks: the
host-resize counter has no series until a still takes that path)."""


def read(ctx):
    c = ctx["counters"]
    if not any(k.startswith("sd_thumbnail_frames_total") for k in c):
        return None
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not images:
        return None
    on_host = sum(v for k, v in c.items()
                  if k.startswith("sd_thumbnail_host_resize_total"))
    return 100.0 * on_host / images
