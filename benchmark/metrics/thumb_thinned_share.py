"""`sd_thumbnail_frames_total{path}`: of the decoded frames that reached
the resize in the window, the share the host had thinned by a stride
first (path=thinned: a side over the longest the device path takes
whole), in percent. 0 where every frame went whole. None on a program
without the counter (the one before ISSUE 38 thinned a frame over 4096
a side and counted nothing), or where no frame was decoded."""


def read(ctx):
    c = ctx["counters"]
    whole = c.get("sd_thumbnail_frames_total{path=whole}", 0.0)
    thinned = c.get("sd_thumbnail_frames_total{path=thinned}", 0.0)
    if not whole + thinned:
        return None
    return 100.0 * thinned / (whole + thinned)
