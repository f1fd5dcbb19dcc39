"""(slowest - fastest timed pass) / median pass, in percent; None with
one pass in the window."""

import statistics


def read(ctx):
    times = [p["cycle_s"] for p in ctx["passes"]]
    if len(times) < 2:
        return None
    return 100.0 * (max(times) - min(times)) / statistics.median(times)
