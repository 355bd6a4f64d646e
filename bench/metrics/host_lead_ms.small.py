"""Mean ms, on the host's clock, from the start of the benchmark's span
around a call to the call's first program launch: the host prologue of
``hybrid_sort`` (``np.asarray(keys)``, planning) before it launches
anything."""
from bench import reduce


def read(run):
    if run.trace is None:
        return None
    lead = reduce.host_lead_ns(run.trace, run.event_map)
    return None if lead is None else lead / 1e6
