"""Device ms per call of the ops in the program's ``local_sort/copy_back``
scope: the run copies that scatter each class's sorted rows back over the
keys and values (``repro.kernels.ops.apply_run_copies``)."""
from bench import stages


def read(run):
    return stages.scope_ms(run, "local_sort/copy_back")
