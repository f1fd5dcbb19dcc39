"""`sd_thumbnail_device_calls_total{bucket,out}` per timed pass: device
calls of the resize, every rung and output canvas together. A bucket's
group of a chunk is one call, or as many as the program's byte bound on
a call's canvases makes of it. None on a program without the counter."""


def read(ctx):
    calls = sum(v for k, v in ctx["counters"].items()
                if k.startswith("sd_thumbnail_device_calls_total"))
    if not calls or not ctx["passes"]:
        return None
    return calls / len(ctx["passes"])
