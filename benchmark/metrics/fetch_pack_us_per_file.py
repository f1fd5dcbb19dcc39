"""`sd_identifier_stage_seconds{stage=pack}` per file hashed: bucketing
the window's messages and `pack_canonical_batch` into the padded batch
array (the `cas.pack` spans)."""


def read(ctx):
    secs = ctx["counters"].get("sd_identifier_stage_seconds{stage=pack}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
