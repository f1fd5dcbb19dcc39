"""`sd_thumbnail_pack_bytes_total` per thumbnail: the bytes `pack` wrote
into staging canvases, each frame and the margin of replicated edge the
filter reads (a 1920 x 1080 RGB clip's frame is 6,220,800; the program
before PR 33 filled the whole 2048 x 2048 canvas, 12.6 MB, and an alpha
canvas beside it). None on a program without the counter."""


def read(ctx):
    wrote = ctx["counters"].get("sd_thumbnail_pack_bytes_total")
    images = sum(p["summary"]["thumbnailer_generated"] for p in ctx["passes"])
    if not wrote or not images:
        return None
    return wrote / images
