"""Compile requests JAX saw inside the window; must read 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
