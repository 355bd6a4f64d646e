"""Device ms per call that no kernel layer covers: the XLA ops of the pass
planner (``repro.core.plan`` bookkeeping, ping-pong buffers, unpadding)
outside the counting-pass kernels, the prologue histogram and the local
sort's stage."""
from bench import reduce


def read(run):
    if run.trace is None:
        return None
    return reduce.per_call_ns(run.trace, reduce.PLANNER, run.event_map) / 1e6
