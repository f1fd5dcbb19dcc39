"""Harness clock around the program warm-up and the warm-up pass."""


def read(ctx):
    parts = ctx["setup_parts"]
    return parts["program_warm_s"] + parts["warm_pass_s"]
