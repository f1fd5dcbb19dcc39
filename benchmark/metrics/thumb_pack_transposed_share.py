"""`sd_thumbnail_pack_frames_total{copy}`: of the frames `pack` wrote in
the window whose rows were not contiguous (a portrait turned into its
canvas, the planes of an RGBA still), the share that went in through the
blocked transpose (copy=transposed) and not plane by plane
(copy=planes), in percent. Frames written as one copy of their rows
(copy=rows) do not count. None on a program without the counter, or
where every frame went in as one copy."""


def read(ctx):
    c = ctx["counters"]
    transposed = c.get("sd_thumbnail_pack_frames_total{copy=transposed}", 0.0)
    planes = c.get("sd_thumbnail_pack_frames_total{copy=planes}", 0.0)
    if not transposed + planes:
        return None
    return 100.0 * transposed / (transposed + planes)
