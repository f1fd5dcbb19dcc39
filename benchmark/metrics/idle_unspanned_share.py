"""Of the device's idle seconds inside the window, the share that no
`sd.*` span of the program covers, in percent (`span_reduce.py`): the
part of the host's time that has no name yet."""

from benchmark.span_reduce import for_run


def read(ctx):
    spans = for_run(ctx)
    if not spans or not spans["idle_s"]:
        return None
    return 100.0 * spans["unspanned_s"] / spans["idle_s"]
