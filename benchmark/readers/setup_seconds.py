"""Process start of the benchmark to the first instant of the window."""


def read(ctx):
    return ctx["setup_s"]
