"""Of the bytes of the padded batch arrays handed to the device
(`sd_cas_dispatch_bytes_total`, rung x chunks x 1,024 each: what crosses
the link), the share that is padding: 1 - the message bytes the feeder
staged (`sd_feeder_h2d_bytes_total`) / the dispatched bytes. None on a
program without the counter."""

HEAD = "sd_cas_dispatch_bytes_total{"


def dispatched_bytes(counters: dict) -> float:
    return sum(v for k, v in counters.items() if k.startswith(HEAD))


def read(ctx):
    dispatched = dispatched_bytes(ctx["counters"])
    staged = ctx["counters"].get("sd_feeder_h2d_bytes_total")
    if not dispatched or not staged:
        return None
    return 100.0 * (1.0 - staged / dispatched)
