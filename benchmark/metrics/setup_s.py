"""setup_s: process start to the window (PERF.md §2)."""


def read(ctx):
    return ctx["setup_s"]
