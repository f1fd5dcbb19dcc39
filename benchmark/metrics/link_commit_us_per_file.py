"""`sd_identifier_stage_seconds{stage=db}` per file identified."""


def read(ctx):
    secs = ctx["counters"].get("sd_identifier_stage_seconds{stage=db}.sum")
    files = ctx["hashed"]["files"]
    if not secs or not files:
        return None
    return 1e6 * secs / files
