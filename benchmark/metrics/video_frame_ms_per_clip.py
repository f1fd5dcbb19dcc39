"""`sd_thumbnail_video_seconds{part=frame}` per clip decoded: the seconds
on a decode worker inside the native (libav) or cv2 call that opens the
clip, seeks a tenth of the way in and hands one frame on as RGB. None on
a program without the counter."""


def clips_decoded(counters: dict) -> float:
    """`sd_thumbnail_video_frames_total{result=ok}`, either decoder."""
    return sum(v for k, v in counters.items()
               if k.startswith("sd_thumbnail_video_frames_total{")
               and "result=ok" in k)


def read(ctx):
    counters = ctx["counters"]
    secs = counters.get("sd_thumbnail_video_seconds{part=frame}")
    frames = clips_decoded(counters)
    if not secs or not frames:
        return None
    return 1e3 * secs / frames
