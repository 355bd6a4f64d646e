"""Chip benchmark of the hybrid radix sort: one run of one cell.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``) and a
traffic mix (``bench/workloads``).  A run

1. imports JAX and the program, and fails unless JAX sees a TPU with as many
   chips as the cell asks for and the chip's kind is in ``bench/peaks.json``;
2. makes the cell's pool of inputs on the device from ``--seed``;
3. warms up with one call, so that every program the window runs is built;
   1-3 are the set-up, ``setup_s``;
4. calls the cell's timed path (``bench/entries``; for the one-chip cells
   ``repro.core.hybrid_sort`` with default arguments) in a closed loop,
   one caller, cycling over the pool, for ``--seconds``: each call gets a
   fresh device copy of its input and is timed from the call to
   ``block_until_ready`` of its outputs.  The outputs of a sample of the
   calls, drawn from the seed, stay on the device until the window closes
   (a bulk window keeps every call); the others are dropped.  Nothing is
   copied to the host in the window;
5. after the window reads the devices' peak memory, and with ``--trace 1``
   reduces the profiler trace of the window (``bench/reduce.py``),
   cross-checks the counting passes against what the program reports and
   times one ``jax.lax.sort`` of the same records for information;
6. copies the sample to the host, compares it with the NumPy reference
   (``bench/reference.py``), prints each compared number beside its limit,
   and prints one JSON result line last.

Each metric is read by its own file, ``bench/metrics/<name>.py``, whose
``read(run)`` gets a ``Run``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics of ``BENCHMARK.json``, with ``--trace 1`` its
per-layer metrics.  A reader that finds nothing to read returns ``None`` and
the metric is left out.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, List, NamedTuple, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reduce, reference, traffic  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The window keeps the outputs of its first KEEP_CALLS calls, and of a
# sample of the later ones, on the device for the check after it, up to
# KEEP_BYTES a chip (``window``).
KEEP_CALLS = 16
KEEP_BYTES = 1 << 30


class Run(NamedTuple):
    """What a metric's reader sees of one run."""
    cell: traffic.Cell
    calls: List[tuple]          # host clock (start, end) of each timed call
    setup_s: float
    base_bytes: int             # bytes the harness held on the fullest
                                # chip during the last call: the pool, the
                                # call's input and the sample's outputs
    peak_bytes: int             # that chip's peak after the window
    trace: Optional[dict]       # reduced trace (--trace 1) or None
    event_map: dict             # bench/events.json
    peak: dict                  # bench/peaks.json entry of this chip


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def require_chip(chips: int) -> list:
    """The devices of a TPU with at least ``chips`` chips, or ``NoChip``."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


def load_peak(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return peaks[device_kind]


def cell_metrics(benchmark: dict, cell: str, traced: bool) -> List[dict]:
    """The metric entries ``cell`` reports in this kind of run."""
    group = benchmark["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str) -> Callable:
    return traffic.load_module("metrics", name).read


class CompileCounter:
    """Counts programs built (compiled or loaded from the persistent cache)
    and persistent-cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.built = self.hits = self.misses = 0

    def _duration(self, event, duration_secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.built += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def fresh(keys, vals):
    """A new device copy of one pool input, as a caller with new data holds
    it.  A reused array would keep the host copy that the sort's prologue
    made of it (``np.asarray(keys)``), and later calls would skip that
    round trip."""
    keys = jnp.copy(keys)
    vals = None if vals is None else jnp.copy(vals)
    return jax.block_until_ready((keys, vals))


def bytes_in_use(devices: list, key: str = "bytes_in_use") -> List[int]:
    return [(d.memory_stats() or {}).get(key, 0) for d in devices]


def device_bytes(out, devices: list) -> List[int]:
    """Bytes of the arrays in ``out`` on each of ``devices``."""
    held = [0] * len(devices)
    for leaf in jax.tree.leaves(out):
        for shard in leaf.addressable_shards:
            if shard.device in devices:
                held[devices.index(shard.device)] += shard.data.nbytes
    return held


def window(entry: traffic.Entry, pool: list, seconds: float, keep: int,
           rng: random.Random, devices: list):
    """The closed loop: returns the host-clock span of each call, a sample
    of the calls' outputs as ``(call index, outputs)``, and the bytes those
    held on each chip during the last call.  Each call gets a fresh copy of
    its pool input, made before its timing starts.  The sample holds each
    of the first ``keep`` calls and then call ``i`` with probability
    ``keep / (i + 1)``, drawn with ``rng``, while its outputs fit in
    ``KEEP_BYTES`` a chip.  They stay on the device until the window has
    closed and are never freed in it, so that no call waits for a copy to
    the host or competes with one, and device memory fills the same way in
    every run."""
    calls, kept = [], []
    held = [0] * len(devices)
    start = time.perf_counter()
    i = 0
    while True:
        keys, vals = fresh(*pool[i % len(pool)])
        during = list(held)
        with jax.profiler.TraceAnnotation(reduce.CALL_SPAN):
            t0 = time.perf_counter()
            out = jax.block_until_ready(entry.sort(keys, vals))
            t1 = time.perf_counter()
        del keys, vals
        calls.append((t0, t1))
        if rng.random() * (i + 1) < keep:
            grown = [a + b for a, b in zip(held, device_bytes(out, devices))]
            if max(grown) <= KEEP_BYTES:
                kept.append((i, out))
                held = grown
        del out
        i += 1
        if t1 - start >= seconds:
            return calls, kept, during


def check_outputs(cell: traffic.Cell, pool: list, outputs: list):
    """Compare each ``(call index, outputs on the host)`` with the
    reference; failures and the compared numbers summed over them."""
    host = traffic.host_pool(pool)
    want = [reference.canonical(k, v) for k, v in host]
    totals = {"keys_wrong": 0}
    if cell.with_values:
        totals["pairs_wrong"] = 0
    failed = 0
    for i, (keys_out, vals_out) in outputs:
        got = reference.compare(want[i % len(pool)], keys_out,
                                vals_out if cell.with_values else None)
        failed += any(got.values())
        for k, v in got.items():
            totals[k] += v
    return failed, totals


def trace_window(*args, log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(log_dir, profiler_options=opts):
        return window(*args)


def stats_passes(passes: Callable, pool: list, n_calls: int) -> List[int]:
    """Counting passes the program reports for the input of each call."""
    per_input = [passes(keys, vals)
                 for keys, vals in pool[:min(len(pool), n_calls)]]
    return [per_input[i % len(per_input)] for i in range(n_calls)]


def lax_sort_seconds(keys, vals) -> float:
    """One compiled ``jax.lax.sort`` of the same records (information)."""
    ops = (keys,) if vals is None else (keys, vals)
    fn = jax.jit(lambda *a: jax.lax.sort(a, num_keys=1))
    jax.block_until_ready(fn(*ops))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*ops))
    return time.perf_counter() - t0


def run_cell(cell: traffic.Cell, seed: int, seconds: float, traced: bool,
             metrics: List[dict], entry: traffic.Entry, devices: list,
             peak: dict) -> dict:
    """Set up, measure, check; returns the result line as a dict.
    ``devices`` are the chips the cell uses."""
    from repro.utils.compile_cache import enable_compile_cache

    t_import = time.perf_counter()
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with CompileCounter() as setup_builds:
        pool = traffic.make_pool(cell, seed, entry.sharding)
        t_pool = time.perf_counter()
        warm = fresh(*pool[0])
        base = bytes_in_use(devices)
        jax.block_until_ready(entry.sort(*warm))
        del warm
        t_warm = time.perf_counter()
    setup_s = t_warm - T_START
    say(f"setup: import_s={t_import - T_START:.6f} "
        f"pool_s={t_pool - t_import:.6f} warm_s={t_warm - t_pool:.6f} "
        f"setup_s={setup_s:.6f} programs_built={setup_builds.built} "
        f"cache_hits={setup_builds.hits} cache_misses={setup_builds.misses} "
        f"cache_dir={cache_dir}")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        args = (entry, pool, seconds, KEEP_CALLS, random.Random(seed),
                devices)
        with CompileCounter() as window_builds:
            if traced:
                calls, kept, held = trace_window(*args, log_dir=log_dir)
            else:
                calls, kept, held = window(*args)
        peaks = bytes_in_use(devices, "peak_bytes_in_use")
        # what the harness held during the last call: the pool and the
        # call's input (``base``), and the sample's outputs (``held``)
        base = [a + b for a, b in zip(base, held)]
        fullest = max(range(len(devices)), key=lambda d: peaks[d] - base[d])
        ms = [1e3 * (b - a) for a, b in calls]
        span = calls[-1][1] - calls[0][0]
        between = sum(b[0] - a[1] for a, b in zip(calls, calls[1:]))
        say(f"window: calls={len(calls)} seconds={span:.6f} "
            f"median_ms={statistics.median(ms):.6f} "
            f"compiles_in_window={window_builds.built}")
        say(f"harness: between_calls_s={between:.6f} "
            f"share_of_window={100 * between / span:.6f}%")
        say(f"memory: base_bytes={base} (sample held {held}) "
            f"peak_bytes={peaks} input_bytes={cell.n * cell.record_bytes}")
        trace = None
        if traced:
            trace = reduce.reduce_xspace(reduce.find_xspace(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    event_map = reduce.load_event_map()
    device = devices[0]
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": max(peaks)}
    breakdown = None
    if traced:
        if len(trace["calls"]) != len(calls):
            raise RuntimeError(f"trace holds {len(trace['calls'])} calls, "
                               f"the window made {len(calls)}")
        lo, hi = reduce.window(trace)
        device_info["busy_s"] = reduce.busy_ns(trace) / 1e9
        device_info["window_s"] = (hi - lo) / 1e9
        if entry.counting_passes is not None:
            traced_passes = reduce.count_per_call(trace, "counting pass",
                                                  event_map)
            program_passes = stats_passes(entry.counting_passes, pool,
                                          len(calls))
            say(f"trace: calls by counting passes "
                f"{sorted(collections.Counter(traced_passes).items())}, "
                f"program reports "
                f"{sorted(collections.Counter(program_passes).items())}")
            if traced_passes != program_passes:
                raise RuntimeError("the trace's counting-pass launches "
                                   "disagree with what the program reports")
        lax_s = lax_sort_seconds(*pool[0])
        say(f"reference (information, not a metric): jax.lax.sort of the "
            f"same {cell.n} records: {lax_s:.6f} s; timed path median "
            f"{statistics.median(ms) / 1e3:.6f} s")
        breakdown = {"device_ops": reduce.top_ops(trace),
                     "idle_gaps": reduce.idle_gaps(trace)}

    outputs = [(i, entry.to_host(out)) for i, out in kept]
    del kept
    say(f"checked: {len(outputs)} of {len(calls)} calls, a sample drawn "
        f"from the seed: {[i for i, _ in outputs]}")
    failed, compared = check_outputs(cell, pool, outputs)
    run = Run(cell=cell, calls=calls, setup_s=setup_s,
              base_bytes=base[fullest], peak_bytes=peaks[fullest],
              trace=trace, event_map=event_map, peak=peak)
    values = {}
    for m in metrics:
        value = load_reader(m["name"])(run)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    limits = {name: {"value": v, "limit": 0} for name, v in compared.items()}
    result = {"correct": failed == 0 and all(v == 0 for v in compared.values()),
              "attempted": len(calls), "failed": failed, "metrics": values,
              "device": device_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = limits
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    benchmark = load_benchmark()
    cell = traffic.load_cell(args.workload, benchmark)
    try:
        devices = require_chip(cell.chips)[:cell.chips]
        peak = load_peak(devices[0].device_kind)
    except (NoChip, KeyError) as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    entry = traffic.load_entry(cell, devices)
    say(f"device: {devices[0].device_kind} x{jax.device_count()}; {cell!r}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      cell_metrics(benchmark, cell.name, bool(args.trace)),
                      entry, devices, peak)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
