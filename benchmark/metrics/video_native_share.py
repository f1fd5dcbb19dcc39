"""`sd_thumbnail_video_frames_total{decoder,result=ok}`: of the clips'
frames, the share the native libav frontend decoded (the rest fell to
cv2, which takes the exact frame and not the key frame before it). None
on a program without the counter."""


def read(ctx):
    c = ctx["counters"]
    native = c.get("sd_thumbnail_video_frames_total{decoder=native,result=ok}",
                   0.0)
    cv2 = c.get("sd_thumbnail_video_frames_total{decoder=cv2,result=ok}", 0.0)
    if not native + cv2:
        return None
    return 100.0 * native / (native + cv2)
