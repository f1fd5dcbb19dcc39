"""`sd_thumbnail_heif_seconds{part=plane}` per HEIF frame decoded: the
seconds on a decode worker inside the media job's tap, which makes the
embedder's 32 x 32 plane from the full-size RGBA array (`Image.fromarray`,
`convert("RGB")`, one resize). None on a program without the counter,
or where no frame was tapped."""

from benchmark.metrics.heif_decode_ms_per_image import frames_decoded


def read(ctx):
    counters = ctx["counters"]
    secs = counters.get("sd_thumbnail_heif_seconds{part=plane}")
    frames = frames_decoded(counters)
    if not secs or not frames:
        return None
    return 1e3 * secs / frames
