"""`sd_identifier_batch_fill_ratio`, mean of the window's observations in
percent: the rows an identify window fetched over the limit the live
policy handed `_window_limit` (one observation a window taken). A window
the controller widened past the rows that were left reads low. None where
the family is absent or no window was taken."""


def read(ctx):
    c = ctx["counters"]
    total = c.get("sd_identifier_batch_fill_ratio.sum")
    count = c.get("sd_identifier_batch_fill_ratio.count")
    if total is None or not count:
        return None
    return 100.0 * total / count
