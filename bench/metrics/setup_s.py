"""Seconds from the start of the process to the start of the window: key
generation, bulk load, traffic generation, warm-up (and compiles)."""


def read(run):
    return run.setup_s
