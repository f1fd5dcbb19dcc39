"""`sd_thumbnail_staging_total{result}`: of the bucket calls of the
window, the share that found its kept staging canvas (result=kept) and
did not map a fresh one (result=mapped: first use, a wider pad, lent to
another call, or evicted by the budget). None on a program without the
counter, or where no call was made."""


def read(ctx):
    c = ctx["counters"]
    kept = c.get("sd_thumbnail_staging_total{result=kept}", 0.0)
    mapped = c.get("sd_thumbnail_staging_total{result=mapped}", 0.0)
    if not kept + mapped:
        return None
    return 100.0 * kept / (kept + mapped)
