"""`sd_embed_stage_seconds` (decode, forward and write) per image."""


def read(ctx):
    c = ctx["counters"]
    secs = sum(v for k, v in c.items()
               if k.startswith("sd_embed_stage_seconds{") and k.endswith(".sum"))
    images = c.get("sd_embed_files_total{result=embedded}")
    if not secs or not images:
        return None
    return 1e3 * secs / images
