"""`sd_identifier_stage_seconds{stage=journal}` per file hashed: the row
loop's `journal.lookup` (one `index_journal` query a non-empty row: the
question the walker asked of the same file in the same pass) with
`bytes_saved` on a hit. None on a program without the stage label."""


def read(ctx):
    c = ctx["counters"]
    key = "sd_identifier_stage_seconds{stage=journal}"
    files = ctx["hashed"]["files"]
    if not c.get(key + ".count") or not files:
        return None
    return 1e6 * c[key + ".sum"] / files
