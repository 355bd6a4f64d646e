"""Device ms per call of the ops in the program's ``pass_bookkeeping``
scope: the bucket bookkeeping of ``repro.core.plan`` around each counting
pass (active segments, merge rows, next-pass table, block descriptors)."""
from bench import stages


def read(run):
    return stages.scope_ms(run, "pass_bookkeeping")
