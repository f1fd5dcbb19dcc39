"""`sd_thumbnail_video_bytes_total` per clip decoded: `nbytes` of the
frame handed to the resize (8,294,400 for 1920 x 1080 RGBA: no decoder
scales a video frame on its way out). None on a program without the
counter."""

from benchmark.metrics.video_frame_ms_per_clip import clips_decoded


def read(ctx):
    counters = ctx["counters"]
    moved = counters.get("sd_thumbnail_video_bytes_total")
    frames = clips_decoded(counters)
    if not moved or not frames:
        return None
    return moved / frames
