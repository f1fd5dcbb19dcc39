"""The resize programs' share of the HBM roofline, in percent: the bytes
the thumbnails need / the chip's HBM bandwidth / the summed device time
of the resize programs (`kernels.json`'s `resize`). The memory side: a
triangle filter reads every pixel of a frame once and writes the
thumbnail; the weights' matmuls are the compute side, which depends on
how the filter is lowered and is not what a thumbnail needs.

`needed_bytes` counts from the configuration, not from what the program
dispatched: w · h · 3 in and tw · th · 3 out for every still the
configuration's generator plans for a pass (a photo by its class's size
as the configuration states it, a screenshot by its own), no canvas, pad
or float32 copy. Never 0; None without a trace, without peaks, or where
no resize program ran."""

import importlib

from benchmark.reference.media import scale_dimensions


def needed_bytes(config: dict) -> int:
    """Bytes one pass's thumbnails need: every still's frame in, its
    thumbnail out, three bytes a pixel. A still is an entry the plan
    gives a size: an `image`, or a kind's own keys (`w`, `h`)."""
    target_px = config["upstream"]["thumbnail"]["target_px"]
    plan = importlib.import_module(
        "benchmark.generators." + config["generator"]).plan
    total = 0
    for entry in plan(config, 0):
        still = entry.get("image") or entry.get(entry.get("kind"))
        if still:
            tw, th = scale_dimensions(still["w"], still["h"], target_px)
            total += 3 * (still["w"] * still["h"] + tw * th)
    return total


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks:
        return None
    kernel = trace["kernels"].get("resize")
    if not kernel or not kernel["seconds"]:
        return None
    try:
        needed = needed_bytes(ctx["config"]) * len(ctx["passes"])
    except KeyError:
        return None  # a configuration that states no roll of stills
    if not needed:
        return None
    return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / kernel["seconds"]
