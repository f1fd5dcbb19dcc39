"""`sd_autotune_decisions_total{workload,action}`, summed, per pass: how
often the closed-loop controller (`parallel/autotune.py`) moved a knob. The
controller's gauges are differenced away by the window (a pass resets them
to where they stood); the counter survives. A labelled counter has no
series before its first tick, so where the program exports the
controller's families and none of them is a decision the reading is 0: it
ran and decided nothing. None where the program has no such family."""

FAMILY = "sd_autotune_decisions_total{"


def read(ctx):
    c = ctx["counters"]
    passes = len(ctx["passes"])
    if not passes or not any(k.startswith("sd_autotune_") for k in c):
        return None
    return sum(v for k, v in c.items() if k.startswith(FAMILY)) / passes
