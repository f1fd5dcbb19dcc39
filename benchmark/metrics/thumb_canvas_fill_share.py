"""`sd_thumbnail_pack_bytes_total` / `sd_thumbnail_canvas_bytes_total`,
in percent: of the canvas bytes the window's device calls put on the
link (pad rows and the canvas round each frame included), the share
`pack` wrote a frame or its margin into. What the rungs' shapes and the
power-of-two pads cost. None on a program without the counters."""


def read(ctx):
    c = ctx["counters"]
    wrote = c.get("sd_thumbnail_pack_bytes_total")
    canvases = sum(v for k, v in c.items()
                   if k.startswith("sd_thumbnail_canvas_bytes_total"))
    if not wrote or not canvases:
        return None
    return 100.0 * wrote / canvases
