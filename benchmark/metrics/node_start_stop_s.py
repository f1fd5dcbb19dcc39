"""Seconds per pass inside `Node.start` and `Node.shutdown`, from their
spans (`sd_span_seconds{stage=node.start}`, `{stage=node.shutdown}`):
the program's part of `between_jobs_s`."""

from benchmark.span_reduce import counter


def read(ctx):
    start = counter(ctx["counters"], "node.start")
    stop = counter(ctx["counters"], "node.shutdown")
    if start is None or stop is None:
        return None
    return (start + stop) / len(ctx["passes"])
