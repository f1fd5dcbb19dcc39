"""`sd_feeder_h2d_bytes_total` per file hashed: padding shows here."""


def read(ctx):
    staged = ctx["counters"].get("sd_feeder_h2d_bytes_total")
    files = ctx["hashed"]["files"]
    if not staged or not files:
        return None
    return staged / files
