"""`sd_identifier_stage_seconds{stage=read}` per file hashed: the sampled
reads (`cas.read_message`) inside the feeder's row loop, timed per file
into a local and observed once per window."""


def read(ctx):
    secs = ctx["counters"].get("sd_identifier_stage_seconds{stage=read}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
