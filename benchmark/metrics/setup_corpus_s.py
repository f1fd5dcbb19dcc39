"""Harness clock around building the location."""


def read(ctx):
    return ctx["setup_parts"]["location_s"]
