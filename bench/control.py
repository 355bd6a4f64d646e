"""The control of the comparison that decides ``correct``: it has to fail.

The system states no precision, so the control breaks one guarantee that
the configurations state: it puts the reference, ``jax.lax.sort``, in the
program's place, but orders the records by the key's top 24 bits only, as
a sort that skipped the last 8-bit digit pass would.  Keys that share
their top 24 bits come out in input order, which ``keys_wrong`` counts.

The benchmark's own runs never run this.  On the chip, one process reads
the control at a cell's own size on several seeds, each through the
harness's own set-up, window and comparison:

    python3 -m bench.control --workload <cell> --seconds 5 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys

import jax

from bench import run, traffic

DROPPED_BITS = 8


def control_sort(keys, values):
    """Records ordered by ``keys >> DROPPED_BITS``, stably."""
    coarse = keys >> DROPPED_BITS
    if values is None:
        return jax.lax.sort((coarse, keys), num_keys=1, is_stable=True)[1], None
    out = jax.lax.sort((coarse, keys, values), num_keys=1, is_stable=True)
    return out[1], out[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    benchmark = run.load_benchmark()
    cell = traffic.load_cell(args.workload, benchmark)
    try:
        devices = run.require_chip(cell.chips)[:cell.chips]
        peak = run.load_peak(devices[0].device_kind)
    except (run.NoChip, KeyError) as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2
    entry = traffic.load_entry(cell, devices)._replace(
        sort=jax.jit(control_sort), counting_passes=None)
    for seed in args.seeds:
        result = run.run_cell(cell, seed, args.seconds, False, [], entry,
                              devices, peak)
        print(json.dumps({"control": cell.name, "seed": seed,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
