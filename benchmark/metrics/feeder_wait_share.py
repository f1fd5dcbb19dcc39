"""`sd_feeder_wait_seconds` as a share of the file_identifier jobs' time,
in percent: how long the consumer stood waiting for a window."""


def read(ctx):
    wait = ctx["counters"].get("sd_feeder_wait_seconds.sum")
    secs = [p["summary"]["job_seconds"].get("file_identifier")
            for p in ctx["passes"]]
    if wait is None or None in secs or not sum(secs):
        return None
    return 100.0 * wait / sum(secs)
