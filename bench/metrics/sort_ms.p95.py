"""95th percentile, in ms, of the host-clock time of every call in the
window, each from the call to ``block_until_ready`` of its outputs."""
import statistics


def read(run):
    ms = [1e3 * (b - a) for a, b in run.calls]
    if len(ms) < 2:
        return None
    return statistics.quantiles(ms, n=20, method="inclusive")[18]
