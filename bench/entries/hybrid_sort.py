"""The timed path of a one-chip cell: ``repro.core.hybrid_sort`` with
default arguments, as a user calls it, on the first chip."""
import jax

from bench import traffic


def build(cell, devices):
    from repro.core import hybrid_sort

    def sort(keys, values):
        if values is None:
            return hybrid_sort(keys), None
        return hybrid_sort(keys, values)

    def counting_passes(keys, values):
        if values is None:
            _, stats = hybrid_sort(keys, return_stats=True)
        else:
            _, _, stats = hybrid_sort(keys, values, return_stats=True)
        return int(stats.counting_passes)

    return traffic.Entry(sort=sort,
                         sharding=jax.sharding.SingleDeviceSharding(devices[0]),
                         counting_passes=counting_passes)
