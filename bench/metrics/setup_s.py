"""Seconds from the process's first statement to the end of the warm call:
imports, the compile cache, the pool made on the device, one warm call."""


def read(run):
    return run.setup_s
