"""Records sorted per second, in millions: the records of every call in the
window over the host-clock time from the first call's start to the end of
the last call's ``block_until_ready``."""


def read(run):
    calls = run.calls
    return run.cell.n * len(calls) / (calls[-1][1] - calls[0][0]) / 1e6
