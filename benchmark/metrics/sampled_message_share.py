"""`sd_identifier_messages_total{layout}`: of the cas_id messages the
identifier read and queued for hashing, the share that took the sampled
layout (a file over 100 KiB: header + 4 samples + footer, 57,352 bytes,
the 57-chunk bucket) and not the whole file. None on a program without
the counter."""


def read(ctx):
    by_layout = {k: v for k, v in ctx["counters"].items()
                 if k.startswith("sd_identifier_messages_total{")}
    messages = sum(by_layout.values())
    if not messages:
        return None
    sampled = by_layout.get("sd_identifier_messages_total{layout=sampled}", 0.0)
    return 100.0 * sampled / messages
