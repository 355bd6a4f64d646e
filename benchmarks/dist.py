"""Distributed-exchange sweep: devices × n × distribution (BENCH_dist.json).

Times the §5 shard-exchange pipeline (``core.distributed.
make_distributed_sort``: local sorts → sampled splitters → one fused
bucketing pass per shard → capacity-padded all_to_all → high-fan-in merge)
against one-shot ``hybrid_sort`` over the same global array, per simulated
device count — the pod-scale scaling row the ROADMAP item asks for.  Each
device count runs in its own subprocess under
``--xla_force_host_platform_device_count=N`` (fake host devices; the flag
must precede jax init and never touch the parent).

Rows: ``dist/sort/n=<n>/dev=<N>/<dist>/{hybrid,dist}``;
``engines.annotate`` stamps ``ratio_convention`` and
``ratios/dist/sort/.../dist`` = hybrid_us / dist_us (> 1 = the exchange
beats the one-shot sort).  On this CPU container the fake-device all_to_all
is memcpy through shared memory, so the tracked signal is the scaling
*shape* (per-shard work shrinks as 1/N while exchange volume stays 2·n·b)
plus the structural gates in tests/test_launch_count.py, not absolute wins.
Both contenders run the argsort engine so interpret-mode kernel overhead
does not drown the exchange term.

``python -m benchmarks.dist [--smoke|--full] [--out PATH]`` writes
BENCH_dist.json directly (the ``scripts/ci.sh dist`` entry);
``python -m benchmarks.run --dist --json ...`` routes through here too.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import refuse_on_tpu, row
from benchmarks.engines import annotate

DISTS = ("uniform", "zipf", "clustered")

SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, "src")
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.distributed import make_distributed_sort
    from repro.core.hybrid import hybrid_sort
    from repro.data.distributions import (clustered_keys, entropy_keys,
                                          zipf_keys)

    cfg = json.loads(sys.argv[1])
    ndev = jax.device_count()
    assert ndev == cfg["ndev"], (ndev, cfg["ndev"])
    mesh = jax.make_mesh((ndev,), ("data",))
    GEN = {"uniform": lambda seed, n: entropy_keys(seed, n, 0),
           "zipf": lambda seed, n: zipf_keys(seed, n, a=1.2),
           "clustered": lambda seed, n: clustered_keys(seed, n, clusters=64)}

    def timeit(fn, x, iters=3):
        jax.block_until_ready(fn(x))                     # compile + warm
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    for n in cfg["ns"]:
        dfn = jax.jit(make_distributed_sort(mesh, "data", engine="argsort"))
        hfn = jax.jit(lambda a: hybrid_sort(a, engine="argsort"))
        for seed, dist in enumerate(cfg["dists"]):
            x = jnp.asarray(GEN[dist](seed, n))
            stem = f"dist/sort/n={n}/dev={ndev}/{dist}"
            print(f"ROW {stem}/hybrid {timeit(hfn, x) * 1e6:.3f}", flush=True)
            print(f"ROW {stem}/dist {timeit(dfn, x) * 1e6:.3f}", flush=True)
""")


def _collect_ndev(ndev: int, ns, dists) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    cfg = json.dumps({"ndev": ndev, "ns": list(ns), "dists": list(dists)})
    res = subprocess.run([sys.executable, "-c", SCRIPT, cfg], env=env,
                         capture_output=True, text=True, timeout=3600)
    out = {}
    for line in res.stdout.splitlines():
        if line.startswith("ROW "):
            _, name, us = line.split()
            out[name] = float(us)
    if not out:
        raise RuntimeError(
            f"dist sweep produced no rows at ndev={ndev}:\n"
            f"{res.stderr[-2000:]}")
    return out


def collect(fast: bool = True, smoke: bool = False) -> dict:
    refuse_on_tpu("benchmarks.dist")
    if smoke:
        ndevs, ns, dists = (8,), (1 << 12,), ("uniform",)
    elif fast:
        ndevs, ns, dists = (2, 8), (1 << 14,), DISTS
    else:
        ndevs, ns, dists = (2, 8, 16, 48), (1 << 16,), DISTS
    out = {}
    for ndev in ndevs:
        out.update(_collect_ndev(ndev, ns, dists))
    return annotate(out, baseline="hybrid", contender="dist")


def main(fast: bool = True, smoke: bool = False) -> dict:
    rows = collect(fast, smoke=smoke)
    for name, us in rows.items():
        if not isinstance(us, float):        # notes, ratio_convention
            continue
        if name.startswith("ratios/"):
            row(name, 0.0, f"{us:.3f}x-hybrid-over-dist")
            continue
        n = int(name.split("n=")[1].split("/")[0])
        row(name, us, f"{1e3 * us / n:.2f}ns/key")
    for note in rows["notes"]:
        print(f"# WARNING {note}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_dist.json")
    args = ap.parse_args()
    rows = main(fast=not args.full, smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=2, sort_keys=True)
    print(f"# wrote {args.out}", file=sys.stderr)
