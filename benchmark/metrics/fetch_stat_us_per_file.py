"""`sd_identifier_stage_seconds{stage=stat}` per file hashed: every
`journal.stat_identity` of the feeder's row loop (one a row, vouched rows
and empty files too), timed into a local and observed once per window.
None on a program without the stage label."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_identifier_stage_seconds{stage=stat}"
    files = ctx["hashed"]["files"]
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
