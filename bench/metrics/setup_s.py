"""Seconds from process start to the window: start-up, table creation,
data generation, load, compiles and warm-up (host clock)."""


def read(run):
    return run.setup_s, 1
