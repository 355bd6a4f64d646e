"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses paper-scale sizes
(slow on one CPU core); the default is a reduced but structurally identical
sweep; ``--smoke`` shrinks the engine sweep to a CI-sized single point.
``--json [PATH]`` additionally runs the engine-comparison sweep (argsort vs
fused Pallas kernel engine) and writes the rows to PATH (default
``BENCH_hybrid.json``) so the perf trajectory is machine-readable.  The JSON
is self-interpreting: alongside the raw ``{name: us_per_call}`` rows it
carries ``ratios/...`` speedup entries (argsort / kernel, > 1 means the
kernel engine wins) and a ``notes`` list that is non-empty whenever the
kernel engine regresses below the argsort baseline.

``--ooc`` adds the §5 out-of-core sweep (chunked kernel-engine pipeline +
streaming k-way merge vs one-shot argsort, ``benchmarks.ooc``); with
``--json PATH`` its rows land in ``BENCH_ooc.json`` next to PATH, carrying
the same ``ratios/...`` + ``notes`` contract.  ``--spill`` extends the ooc
sweep with the host-spill regime rows (streamed merge through bounded
device slabs vs device-resident merge vs one-shot argsort).  ``--faults``
adds the resilience-overhead rows (plain vs checksummed+checkpointed vs
injected-fault spill runs, gated ≤ 1.15x on the fault-free path).

``--dist`` adds the distributed-exchange sweep (``benchmarks.dist``): the
§5 shard exchange vs one-shot ``hybrid_sort`` per simulated device count
(fake-device subprocesses); with ``--json PATH`` its rows land in
``BENCH_dist.json`` next to PATH, devices × n × distribution with the same
``ratios/...`` + ``ratio_convention`` + ``notes`` contract.

``--entropy`` adds the entropy-ladder sweep (``benchmarks.entropy``):
adaptive vs static kernel-engine times plus executed-vs-nominal pass counts
per Thearling rung, as ``entropy/...`` rows merged into the same
BENCH_hybrid.json (``ratios/entropy/.../adaptive`` > 1 means pass elision
pays; the gate is >= 1.3x on low-entropy rungs, <= 1.05x regression on
uniform).

A module that raises prints ``<name>/ERROR`` and the runner exits non-zero
after the sweep.

``python -m benchmarks.run [--full] [--smoke] [--only fig6,...]
                           [--json [PATH]] [--entropy] [--ooc] [--spill]
                           [--faults] [--dist]``
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


MODULES = ["fig2_histogram", "fig6_entropy", "fig7_sizes", "fig8_pipeline",
           "fig10_latest", "ablations", "model_table", "moe_dispatch",
           "roofline", "engines"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: engine sweep only, one small size")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", nargs="?", const="BENCH_hybrid.json",
                    default=None, metavar="PATH",
                    help="write the engine-sweep rows to PATH as JSON")
    ap.add_argument("--entropy", action="store_true",
                    help="also run the entropy-ladder adaptive-vs-static "
                         "sweep (entropy/... rows in BENCH_hybrid.json)")
    ap.add_argument("--ooc", action="store_true",
                    help="also run the out-of-core sweep (BENCH_ooc.json)")
    ap.add_argument("--spill", action="store_true",
                    help="with --ooc: add the host-spill streamed-merge rows")
    ap.add_argument("--faults", action="store_true",
                    help="with --ooc: add the resilience-overhead rows "
                         "(checksums + checkpoints vs plain spill)")
    ap.add_argument("--dist", action="store_true",
                    help="also run the distributed-exchange device-scaling "
                         "sweep (BENCH_dist.json)")
    args = ap.parse_args()
    if args.spill and not args.ooc:
        ap.error("--spill extends the out-of-core sweep: pass --ooc too")
    if args.faults and not args.ooc:
        ap.error("--faults extends the out-of-core sweep: pass --ooc too")
    only = args.only.split(",") if args.only else None
    if args.smoke and only is None:
        only = ["engines"]               # smoke: the acceptance-gated sweep

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    failed = []
    print("name,us_per_call,derived")
    for name in MODULES:
        if only and not any(name.startswith(o) for o in only):
            continue
        if name == "engines" and args.json is not None:
            continue                     # ran below; don't time it twice
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            if name == "engines":
                mod.main(fast=not args.full, smoke=args.smoke)
            else:
                mod.main(fast=not args.full)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{name}/ERROR,0.0,{type(e).__name__}")
            failed.append(name)

    def dump(rows, path):
        with open(path, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True)
        print(f"# wrote {path}", file=sys.stderr)
        if rows["notes"]:
            print(f"# {len(rows['notes'])} regression note(s) in {path}",
                  file=sys.stderr)

    if args.json is not None:
        from benchmarks import engines
        baseline = None
        if os.path.exists(args.json):        # previous sweep = the baseline:
            with open(args.json) as f:       # ratio deltas land in `notes`
                baseline = json.load(f)
        rows = engines.main(fast=not args.full, smoke=args.smoke,
                            baseline=baseline)
        if args.entropy:
            from benchmarks import entropy
            rows = entropy.main(fast=not args.full, smoke=args.smoke,
                                rows=rows)
        dump(rows, args.json)
    elif args.entropy:
        from benchmarks import entropy
        entropy.main(fast=not args.full, smoke=args.smoke)

    if args.ooc:
        from benchmarks import ooc
        rows = ooc.main(fast=not args.full, smoke=args.smoke,
                        spill=args.spill, faults=args.faults)
        if args.json is not None:
            dump(rows, os.path.join(os.path.dirname(args.json) or ".",
                                    "BENCH_ooc.json"))

    if args.dist:
        from benchmarks import dist
        rows = dist.main(fast=not args.full, smoke=args.smoke)
        if args.json is not None:
            dump(rows, os.path.join(os.path.dirname(args.json) or ".",
                                    "BENCH_dist.json"))

    if failed:
        print(f"# {len(failed)} benchmark module(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
