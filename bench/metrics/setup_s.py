"""Seconds from the start of the process to the first request: imports,
device start, compile-cache loads and the warm-up."""


def read(run):
    return run.setup_s
