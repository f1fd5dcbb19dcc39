"""`sd_thumbnail_heif_seconds{part=decode}` per HEIF frame decoded: the
seconds on a decode worker inside the libheif call that reads the
container, decodes the primary item at full size, applies the
container's transforms and hands on RGBA. None on a program without the
counter."""


def frames_decoded(counters: dict) -> float:
    """`sd_thumbnail_heif_frames_total{result=ok}`."""
    return counters.get("sd_thumbnail_heif_frames_total{result=ok}", 0.0)


def read(ctx):
    counters = ctx["counters"]
    secs = counters.get("sd_thumbnail_heif_seconds{part=decode}")
    frames = frames_decoded(counters)
    if not secs or not frames:
        return None
    return 1e3 * secs / frames
