"""`sd_embed_decode_total{scale}`: of the images the embedder decoded,
the share that came through a DCT-scaled JPEG decode (scale 2, 4 or 8)
and not at full size (scale 1). None on a program without the counter."""


def read(ctx):
    by_scale = {k: v for k, v in ctx["counters"].items()
                if k.startswith("sd_embed_decode_total{")}
    images = sum(by_scale.values())
    if not images:
        return None
    full = by_scale.get("sd_embed_decode_total{scale=1}", 0.0)
    return 100.0 * (images - full) / images
