"""Seconds from process start to the first request of the window:
table generation and load, server start, warm-up (compiles included)."""


def read(run):
    return run.setup_s
