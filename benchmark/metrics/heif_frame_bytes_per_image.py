"""`sd_thumbnail_heif_bytes_total` per HEIF frame decoded: `nbytes` of
the frame handed to the resize (48,771,072 for 4032 x 3024 RGBA: libheif
scales nothing on its way out, and hands on an alpha plane whether the
file has one or not). None on a program without the counter."""

from benchmark.metrics.heif_decode_ms_per_image import frames_decoded


def read(ctx):
    counters = ctx["counters"]
    moved = counters.get("sd_thumbnail_heif_bytes_total")
    frames = frames_decoded(counters)
    if not moved or not frames:
        return None
    return moved / frames
