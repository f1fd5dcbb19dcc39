"""`sd_thumbnail_resize_images_total{alpha}`: of the images resized on
the device, the share that went as three colour planes, no alpha plane
beside them. None on a program without the counter."""


def read(ctx):
    counters = ctx["counters"]
    rgb = counters.get("sd_thumbnail_resize_images_total{alpha=0}", 0.0)
    rgba = counters.get("sd_thumbnail_resize_images_total{alpha=1}", 0.0)
    if not rgb + rgba:
        return None
    return 100.0 * rgb / (rgb + rgba)
