"""The hash programs' share of the HBM roofline, in percent: the bytes
the cas_ids need (message bytes in, 32 digest bytes out, counted from
the location) / the chip's HBM bandwidth / the summed device time of the
hash programs. The memory side only: no integer-VPU peak is published
for the v5e."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks:
        return None
    kernel = trace["kernels"].get("hash")
    if not kernel or not kernel["seconds"]:
        return None
    least = ctx["hashed"]["bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least / kernel["seconds"]
