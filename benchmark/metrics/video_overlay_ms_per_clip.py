"""`sd_thumbnail_video_seconds{part=overlay}` per clip decoded: the
seconds on an encode worker that draw the film strips on the resized
frame. None on a program without the counter."""

from benchmark.metrics.video_frame_ms_per_clip import clips_decoded


def read(ctx):
    counters = ctx["counters"]
    secs = counters.get("sd_thumbnail_video_seconds{part=overlay}")
    frames = clips_decoded(counters)
    if not secs or not frames:
        return None
    return 1e3 * secs / frames
