"""Device ms per call of the local sort's stage: the top-level op that
encloses its bitonic kernels (``repro.kernels.bitonic``), with the row
gathers and run copies of ``repro.kernels.ops`` inside it."""
from bench import reduce


def read(run):
    if run.trace is None:
        return None
    ns = reduce.per_call_ns(run.trace, "local sort", run.event_map)
    return ns / 1e6 if ns > 0 else None
