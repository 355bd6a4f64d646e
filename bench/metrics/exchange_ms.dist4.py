"""Device ms per call, on the first chip, of the ops in the distributed
sort's ``exchange`` scope: the shard partition, the capacity-padded buffers
and the ``all_to_all`` collectives (``repro.core.distributed``)."""
from bench import stages


def read(run):
    return stages.scope_ms(run, "exchange")
