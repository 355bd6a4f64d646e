"""Shared benchmark utilities: timing, CSV output, data generators."""
from __future__ import annotations

import time

import numpy as np
import jax


def timeit(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall-clock seconds of fn(*args) (block_until_ready'd)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def refuse_on_tpu(what: str) -> None:
    """Stop a fake-host-device rehearsal when the parent sees a TPU.

    Such benchmarks start child processes with ``--xla_force_host_platform_
    device_count``; on a TPU host the parent already holds the chip, so the
    children would either hang on it or time fake CPU devices.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} is a CPU rehearsal on fake host devices and does not "
            f"run on a TPU; the distributed path's chip run is "
            f"`python chip_smoke.py --chips 4`")


def row(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")


def entropy_label(ands: int) -> str:
    from repro.data.distributions import ENTROPY_BITS_32
    return f"{ENTROPY_BITS_32.get(ands, 0.0):.2f}b"
