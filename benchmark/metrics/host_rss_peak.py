"""Peak RSS of the process and its children inside the window, MiB."""


def read(ctx):
    peak = ctx["host_rss_peak_bytes"]
    return peak / (1 << 20) if peak else None
