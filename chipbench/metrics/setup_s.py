"""Seconds from the process's start to the window's: imports, the
device, weights made from the seed, the engine, and the warm request."""


def read(run):
    return run.setup_s
