"""Set-up: from the process's start to the window's start (weights,
engine, warm-up and compilation or loading from the compile cache)."""


def read(ctx):
    return ctx["setup_s"]
