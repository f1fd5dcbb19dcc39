"""`sd_identifier_stage_seconds{stage=hash}` per file identified: the
host's wait for digests, dispatch and transfer included."""


def read(ctx):
    secs = ctx["counters"].get("sd_identifier_stage_seconds{stage=hash}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
