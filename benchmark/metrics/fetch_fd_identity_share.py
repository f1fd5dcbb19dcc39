"""`sd_identifier_identity_total{source}`: of the stat identities the
feeder's row loop took, the share that came from an `fstat` on the
descriptor the file's bytes were read through (source=descriptor: a file
the journal held no entry for) and not from a `stat` of its path before
any read (source=path: a file the journal knows, and an empty file).
None on a program without the counter, or where no identity was taken."""


def read(ctx):
    c = ctx["counters"]
    by_fd = c.get("sd_identifier_identity_total{source=descriptor}", 0.0)
    by_path = c.get("sd_identifier_identity_total{source=path}", 0.0)
    if not by_fd + by_path:
        return None
    return 100.0 * by_fd / (by_fd + by_path)
