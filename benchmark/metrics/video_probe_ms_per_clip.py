"""`sd_media_extract_seconds{kind=video}` per clip: the media job's
probe of the container for the `media_data` row, a second open after the
thumbnailer's, serial on the job's thread. None on a program without
the counter."""


def read(ctx):
    c = ctx["counters"]
    secs = c.get("sd_media_extract_seconds{kind=video}.sum")
    clips = c.get("sd_media_extract_seconds{kind=video}.count")
    if not secs or not clips:
        return None
    return 1e3 * secs / clips
