"""`sd_media_extract_seconds{kind=heif}` per HEIF file: the media job's
read of the container for the `media_data` row (a second open, after the
thumbnailer's: the size and the EXIF item through libheif, no decode),
serial on the job's thread. None on a program without the counter."""


def read(ctx):
    c = ctx["counters"]
    secs = c.get("sd_media_extract_seconds{kind=heif}.sum")
    files = c.get("sd_media_extract_seconds{kind=heif}.count")
    if not secs or not files:
        return None
    return 1e3 * secs / files
