"""Compile requests (cache hits included) between the window's ends."""


def read(ctx):
    return float(ctx["compiles"].in_window)
