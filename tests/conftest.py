"""Shared test fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches
must see the single real CPU device; multi-device tests spawn subprocesses."""
import os

import jax
import numpy as np
import pytest

# Every CPU executable keeps each of its JIT-compiled kernels mapped, three
# mappings apiece.  An interpret-mode kernel-engine sort program holds
# ~800-1,000 mappings (measured at n <= 1,000 with a kpb=64 config), so a
# process that compiles ~60 of them reaches the default vm.max_map_count of
# 65,530 and the next compile crashes it; a test worker compiles more than
# that.  Past half the limit, drop JAX's compiled-program caches after each
# compile: later calls just recompile.
_MAPS = "/proc/self/maps"


def _release_executables(event, duration, **kwargs):
    del duration, kwargs
    if event != "/jax/core/compile/backend_compile_duration":
        return
    with open(_MAPS) as f:
        if sum(1 for _ in f) > _MAP_BUDGET:
            jax.clear_caches()


if os.path.exists(_MAPS):
    with open("/proc/sys/vm/max_map_count") as f:
        _MAP_BUDGET = int(f.read()) // 2
    jax.monitoring.register_event_duration_secs_listener(_release_executables)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def entropy_keys(rng, n, ands, dtype=np.uint32):
    """Thearling & Smith entropy-reduction benchmark (paper §6): AND together
    1 + ands uniform draws; ands=0 -> uniform, more ANDs -> lower entropy."""
    info = np.iinfo(dtype)
    x = rng.integers(0, info.max, n, dtype=dtype, endpoint=True)
    for _ in range(ands):
        x &= rng.integers(0, info.max, n, dtype=dtype, endpoint=True)
    return x
