"""Device milliseconds per dispatch of the resize programs."""


def read(ctx):
    trace = ctx["trace"]
    kernel = trace and trace["kernels"].get("resize")
    if not kernel or not kernel["dispatches"]:
        return None
    return 1e3 * kernel["seconds"] / kernel["dispatches"]
