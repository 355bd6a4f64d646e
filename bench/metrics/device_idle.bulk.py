"""Per cent of the traced window (first call's start to last call's end) in
which no operation ran on the device."""
from bench import reduce


def read(run):
    return None if run.trace is None else reduce.idle_share(run.trace)
