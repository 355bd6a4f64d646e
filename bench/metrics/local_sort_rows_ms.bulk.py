"""Device ms per call of the ops in the program's ``local_sort/rows``
scope: the gathers of done buckets into the local sort's size-class tables
(``repro.kernels.ops``)."""
from bench import stages


def read(run):
    return stages.scope_ms(run, "local_sort/rows")
