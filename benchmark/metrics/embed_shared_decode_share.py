"""`sd_embed_planes_total{source}`: of the planes the media job's embed
step consumed, the share made from the frame the thumbnailer had just
decoded (source=shared) and not by a decode of its own (source=own).
None on a program without the counter, or where nothing was embedded."""


def read(ctx):
    c = ctx["counters"]
    shared = c.get("sd_embed_planes_total{source=shared}", 0.0)
    own = c.get("sd_embed_planes_total{source=own}", 0.0)
    if not shared + own:
        return None
    return 100.0 * shared / (shared + own)
