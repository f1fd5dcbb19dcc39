"""What of the feeder's row loop no stage holds, per file hashed: the
`identify.rows` span less `sd_identifier_stage_seconds` of the five
stages timed inside it (`read`, `chunk_cache`, `stat`, `journal`,
`rehash`). The loop's own Python: paths, keys, the lists it fills, the
clock pairs. None if any of the five is absent, as on a program from
before the split: a remainder that silently held them would be no
remainder."""

from benchmark.span_reduce import counter

STAGES = ("read", "chunk_cache", "stat", "journal", "rehash")


def read(ctx):
    c = ctx["counters"]
    rows = counter(c, "identify.rows")
    held = [c.get("sd_identifier_stage_seconds{stage=%s}.sum" % s)
            for s in STAGES]
    files = ctx["hashed"]["files"]
    if not rows or None in held or not files:
        return None
    return 1e6 * (rows - sum(held)) / files
