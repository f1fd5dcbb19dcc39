"""`sd_identifier_stage_seconds{stage=chunk_cache}` per file hashed: the
seconds inside `cas.build_chunk_cache` in the feeder's row loop, one
blake2b per KiB of message (57 for a sampled file) for the journal's
dirty-range cache. None on a program without the stage label."""


def read(ctx):
    secs = ctx["counters"].get(
        "sd_identifier_stage_seconds{stage=chunk_cache}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
