"""Tiles the local sort ran per call: the device ops whose name starts with
``bitonic_sort_rows_stable`` (the stable bitonic kernel of
``repro.kernels.ops``, one launch per tile of occupied rows) inside each
call's program execution, averaged over the window's calls.  A program
that launches the kernel once per size class counts its classes."""
from bench import reduce


def read(run):
    if run.trace is None:
        return None
    counts = reduce.count_per_call(run.trace, "local sort", run.event_map)
    return sum(counts) / len(counts)
