"""`sd_identifier_stage_seconds{stage=dispatch}` per file hashed: the host
inside `blake3_jax.hash_batch` (the `cas.enqueue` spans), transfer to
the device and enqueue; the wait for digests is `hash_wait_us_per_file`."""


def read(ctx):
    secs = ctx["counters"].get(
        "sd_identifier_stage_seconds{stage=dispatch}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
