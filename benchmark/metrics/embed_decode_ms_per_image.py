"""`sd_embed_stage_seconds{stage=decode}` per image embedded: the
embedder's full-size decode (PERF.md finding 3), apart from the forward
pass and the write that `embed_ms_per_image` adds to it."""


def read(ctx):
    c = ctx["counters"]
    secs = c.get("sd_embed_stage_seconds{stage=decode}.sum")
    images = c.get("sd_embed_files_total{result=embedded}")
    if not secs or not images:
        return None
    return 1e3 * secs / images
