"""The bytes of the padded batch arrays handed to the device
(`sd_cas_dispatch_bytes_total`) over the host's seconds inside
`blake3_jax.hash_batch` (`sd_identifier_stage_seconds{stage=dispatch}`,
the `cas.enqueue` spans: transfer to the device and enqueue), in GB/s.
The host's view of the link, not a device reading: the device planes
show ops, not copies. None on a program without the counter."""

from benchmark.metrics.hash_pad_share import dispatched_bytes


def read(ctx):
    dispatched = dispatched_bytes(ctx["counters"])
    secs = ctx["counters"].get(
        "sd_identifier_stage_seconds{stage=dispatch}.sum")
    if not dispatched or not secs:
        return None
    return dispatched / secs / 1e9
