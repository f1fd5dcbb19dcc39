"""`sd_feeder_h2d_bytes_total` per file hashed: the bytes of the cas_id
messages the feeder staged for the device, as it counts them before
they are packed into padded batches. Padding does not show here
(`hash_pad_share` has it): `photolib.raw` reads the sampled message's
57,352 to the byte."""


def read(ctx):
    staged = ctx["counters"].get("sd_feeder_h2d_bytes_total")
    files = ctx["hashed"]["files"]
    if not staged or not files:
        return None
    return staged / files
