"""The timed path of a four-chip cell: ``repro.core.distributed``'s sample
sort, jitted with default arguments over a one-axis mesh of the cell's
chips.  Each chip holds ``n / chips`` records of the pool; the outputs are
capacity-padded per chip and joined on the host by ``valid_concat``."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import traffic

AXIS = "data"


def _program(devices):
    from repro.core.distributed import make_distributed_sort

    mesh = Mesh(np.array(devices), (AXIS,))
    return mesh, jax.jit(make_distributed_sort(mesh, AXIS))


def build(cell, devices):
    from repro.core.distributed import valid_concat

    mesh, program = _program(devices)

    def sort(keys, values):
        return program(keys) if values is None else program(keys, values)

    def to_host(out):
        stats = out[-1]
        keys = valid_concat(out[0], stats.valid)
        values = valid_concat(out[1], stats.valid) if len(out) == 3 else None
        return keys, values

    return traffic.Entry(sort=sort, sharding=NamedSharding(mesh, P(AXIS)),
                         counting_passes=None, to_host=to_host)


def lower(cell, devices):
    """The program ``build``'s sort runs, lowered for the cell's records."""
    mesh, program = _program(devices)
    spec = lambda dtype: jax.ShapeDtypeStruct(
        (cell.n,), jnp.dtype(dtype), sharding=NamedSharding(mesh, P(AXIS)))
    if not cell.with_values:
        return program.lower(spec(cell.key_dtype))
    return program.lower(spec(cell.key_dtype), spec(cell.value_dtype))
